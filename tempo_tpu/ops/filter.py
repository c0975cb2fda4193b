"""Data-driven predicate evaluation over vtpu columns: the TraceQL /
tag-search execution kernel.

This replaces the reference's iterator-tree engine (pkg/parquetquery
ColumnIterator/JoinIterator + vparquet/block_search.go pipelines) with
one vectorized pass: every condition becomes a boolean mask over its
axis (span rows, attr rows, resource rows), attr/resource hits scatter
to span rows with a segment-max, masks combine through a static boolean
expression tree on the VPU, and the span mask aggregates to a trace
mask with another segment-max. No Dremel rep/def levels anywhere:
hierarchy is explicit segment ids (SURVEY.md 7.3 "the crux" -- this
layout dissolves it).

Only the STRUCTURE (expression tree + condition targets/ops) keys a jit
compile; operand values -- dictionary codes, thresholds, regex-match
tables -- are traced arrays, so `{span.foo = "bar"}` and
`{span.foo = "baz"}` share one compiled program.

Regex and set predicates use *dictionary tables*: the host evaluates the
regex once over the block's sorted dictionary (the same trick as
parquet dictionary-page pruning, pkg/parquetquery/predicates.go:38-89)
and ships a boolean table; on device the predicate is a single gather.

Device filters are *conservative* (may over-match, never under-match):
clamped int32 / f32 encodings use widened comparisons; conditions whose
encodings can over-match are flagged needs_verify and re-checked
exactly on host over the surviving candidates (db/search.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from .device import scoped

# condition targets
T_SPAN = "span"  # direct span-axis column
T_TRACE = "trace"  # trace-axis column
T_RES = "res"  # resource-axis dedicated column (gathered via span.res_idx)
T_SATTR = "sattr"  # generic span attr table
T_RATTR = "rattr"  # generic resource attr table

# ops: v0/v1 int operands, f0/f1 float operands, table = dict-code table
OPS = (
    "eq", "ne", "ne_present", "lt", "le", "gt", "ge", "range",
    "exists", "ne_clamped", "intable", "notintable",
)


@dataclass(frozen=True)
class Cond:
    """One predicate. Hashable => part of the jit key. needs_verify:
    this condition of the query may over-match on the device and hosteval
    must re-check (defined once, on traceql.plan.PlannedQuery)."""

    target: str
    col: str  # device column ('span.dur_us', 'res.service_id', ...) or
    # value kind for attr targets: 'str', 'int', 'float', 'bool', 'any'
    op: str
    is_float: bool = False
    needs_verify: bool = False


@dataclass
class Operands:
    """Per-condition operand values (traced; NOT part of the jit key).
    ints[i] = (key_code, v0, v1); floats[i] = (f0, f1);
    tables[i] = bool array over dictionary codes (intable ops only)."""

    ints: np.ndarray  # (n_conds, 3) int32
    floats: np.ndarray  # (n_conds, 2) float32
    tables: dict[int, np.ndarray] | None = None

    @classmethod
    def build(cls, rows: list, tables: dict[int, np.ndarray] | None = None) -> "Operands":
        if not rows:
            ints = np.zeros((0, 3), np.int32)
            floats = np.zeros((0, 2), np.float32)
        else:
            ints = np.asarray([[r[0], r[1], r[2]] for r in rows], dtype=np.int64)
            ints = np.clip(ints, -(2**31), 2**31 - 1).astype(np.int32)
            floats = np.asarray([[r[3], r[4]] for r in rows], dtype=np.float32)
        return cls(ints, floats, tables)


_ATTR_VALUE_COL = {"str": "str_id", "int": "int32", "bool": "int32", "float": "f32"}
_VT_CODE = {"str": 0, "int": 1, "float": 2, "bool": 3, "any": -1}

# expression trees: ('cond', i) | ('and', *children) | ('or', *children)
CondTree = tuple


def all_conds_tree(n: int) -> CondTree:
    return ("and",) + tuple(("cond", i) for i in range(n))


def _flatten(conds) -> list:
    out = []
    for g in conds:
        if isinstance(g, Cond):
            out.append(g)
        else:
            out.extend(g)
    return out


def required_columns(conds) -> list[str]:
    # trace.span_off: spans are stored grouped by trace, so span->trace
    # aggregation is cumsum + gather-at-offsets (no scatter; see
    # _offset_counts). trace_sid still feeds the trace->span gather.
    # span@<res col> entries are NOT physical columns: they ask the
    # staging layer to materialize that res column at span level once
    # (query-independent), so the kernel avoids a per-query span-length
    # gather. Readers of raw columns must skip them.
    need = {"span.trace_sid", "trace.span_off"}
    for c in _flatten(conds):
        if c.target in (T_SPAN, T_TRACE):
            need.add(c.col)
        elif c.target == T_RES:
            need.add(c.col)
            need.add("span.res_idx")
            need.add(f"span@{c.col}")
        elif c.target == T_SATTR:
            need.update({"sattr.span", "sattr.key_id", "sattr.vtype"})
            if c.col in _ATTR_VALUE_COL:
                need.add(f"sattr.{_ATTR_VALUE_COL[c.col]}")
        elif c.target == T_RATTR:
            # res.service_id rides along to size the resource axis
            need.update({"rattr.res", "rattr.key_id", "rattr.vtype", "span.res_idx", "res.service_id"})
            if c.col in _ATTR_VALUE_COL:
                need.add(f"rattr.{_ATTR_VALUE_COL[c.col]}")
    return sorted(need)


def _cmp(op: str, x, v0, v1, f0, f1, is_float: bool, table):
    if is_float:
        a, b = f0, f1
    else:
        a, b = v0, v1
    if op == "eq":
        return x == a
    if op == "ne":
        return x != a
    if op == "ne_present":  # value present (code >= 0) and differs
        return (x != a) & (x >= 0)
    if op == "ne_clamped":  # conservative ne on a clamped int encoding
        return (x != a) | (x == 2**31 - 1) | (x == -(2**31) + 1)
    if op == "lt":
        return x < a
    if op == "le":
        return x <= a
    if op == "gt":
        return x > a
    if op == "ge":
        return x >= a
    if op == "range":  # inclusive [a, b]
        return (x >= a) & (x <= b)
    if op == "exists":
        return jnp.ones_like(x, dtype=bool)
    if op in ("intable", "notintable"):
        hit = table[jnp.clip(x, 0, table.shape[0] - 1)] > 0
        if op == "notintable":
            hit = ~hit
        return hit & (x >= 0)
    raise ValueError(f"unknown op {op}")


def _offset_counts(mask, off):
    """Per-segment True counts when rows are GROUPED by segment (the
    vtpu layout: spans sorted by trace, attrs sorted by owner):
    exclusive cumsum + two gathers at the segment offsets. On TPU this
    is a parallel scan instead of a scatter -- XLA lowers segment_sum/
    segment_max over 1M+ rows to a serialized scatter loop that costs
    tens of ms and monopolizes the chip; the scan form is ~10x faster
    and pipelines across concurrent queries. off: (n_seg+1,) rows."""
    ecs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(mask.astype(jnp.int32))]
    )
    return ecs[off[1:]] - ecs[off[:-1]]


def _cond_mask(c: Cond, i, cols, ops_i, ops_f, tables, n_spans_b, n_res_b, valid_span):
    """Span-level mask for one condition."""
    key, v0, v1 = ops_i[i, 0], ops_i[i, 1], ops_i[i, 2]
    f0, f1 = ops_f[i, 0], ops_f[i, 1]
    table = tables.get(i)
    if c.target == T_SPAN:
        return _cmp(c.op, cols[c.col], v0, v1, f0, f1, c.is_float, table) & valid_span
    if c.target == T_RES:
        pre = cols.get(f"span@{c.col}")
        if pre is not None:
            # span-level materialization of the res column (one gather at
            # STAGE time, query-independent, cached) -- a direct compare
            # here instead of a span-length gather per query. The PAD
            # sentinel marks spans with no resource row (idx < 0).
            from .device import PAD_I32

            return (
                _cmp(c.op, pre, v0, v1, f0, f1, c.is_float, table)
                & (pre != PAD_I32)
                & valid_span
            )
        res_mask = _cmp(c.op, cols[c.col], v0, v1, f0, f1, c.is_float, table)
        idx = jnp.clip(cols["span.res_idx"], 0, res_mask.shape[0] - 1)
        return res_mask[idx] & (cols["span.res_idx"] >= 0) & valid_span
    if c.target in (T_SATTR, T_RATTR):
        pre = c.target
        key_match = cols[f"{pre}.key_id"] == key
        if c.col == "any":
            row_hit = key_match
        else:
            vcol = cols[f"{pre}.{_ATTR_VALUE_COL[c.col]}"]
            vt_ok = cols[f"{pre}.vtype"] == _VT_CODE[c.col]
            row_hit = key_match & vt_ok & _cmp(c.op, vcol, v0, v1, f0, f1, c.is_float, table)
        if pre == T_SATTR:
            over = cols.get("sattr.over")
            if over is not None:
                # slot-major (ops/stage._assemble): planes of n_spans_b,
                # plane j holding every span's j-th attribute row, then
                # the slice's overflow rows, owned by `over`. A span
                # matches iff any of its slots hits: an element-wise OR
                # a plane and a scatter of the overflow rows alone (none
                # where counts are even) -- no cumsum over attribute
                # rows, no span-length gather
                n_head = row_hit.shape[0] - over.shape[0]
                hit = jnp.zeros(n_spans_b, bool)
                for lo in range(0, n_head, n_spans_b):
                    hit |= row_hit[lo:lo + n_spans_b]
                if over.shape[0]:  # a padded row's owner is n_spans_b: dropped
                    hit = hit.at[over].max(row_hit[n_head:], mode="drop",
                                           indices_are_sorted=True)
                return hit & valid_span
            if "sattr.off" in cols:  # grouped-by-span rows: scan, no scatter
                return (_offset_counts(row_hit, cols["sattr.off"]) > 0) & valid_span
            owner = jnp.clip(cols["sattr.span"], 0, n_spans_b - 1)
            return (
                jax.ops.segment_max(row_hit.astype(jnp.int32), owner, num_segments=n_spans_b) > 0
            ) & valid_span
        if "rattr.off" in cols:
            res_mask = _offset_counts(row_hit, cols["rattr.off"]) > 0
        else:
            owner = jnp.clip(cols["rattr.res"], 0, n_res_b - 1)
            res_mask = (
                jax.ops.segment_max(row_hit.astype(jnp.int32), owner, num_segments=n_res_b) > 0
            )
        idx = jnp.clip(cols["span.res_idx"], 0, n_res_b - 1)
        return res_mask[idx] & (cols["span.res_idx"] >= 0) & valid_span
    raise ValueError(f"bad target {c.target}")


def attr_reduce_route(conds, cols) -> tuple[str, str] | None:
    """How a launch over `cols` turns generic span-attribute row hits
    into a span mask (_cond_mask's T_SATTR branch) -> (engine, reason),
    counted as the `attr_reduce` routing row; None when no condition has
    that target. Read off the columns: `slots` is what ops/stage stages
    (`skewed_counts` when the slice has overflow rows to scatter),
    `offsets` a caller's own flat rows."""
    if not any(c.target == T_SATTR for c in conds):
        return None
    from ..util.kerneltel import TEL

    over = cols.get("sattr.over")
    if over is not None:
        route = ("slots", "skewed_counts" if over.shape[0] else "dense_counts")
    elif "sattr.off" in cols:
        route = ("offsets", "flat_rows")
    else:
        route = ("offsets", "no_offsets")
    TEL.record_routing("attr_reduce", *route)
    return route


def normalize_tree(tree: CondTree, conds: tuple[Cond, ...]) -> CondTree:
    """Lift a mixed tree into trace-level form: pure-span subtrees wrap in
    ('tracify', t); trace-target conds stay direct. A mix below an 'or'
    of span and trace conds is allowed: the span side tracifies."""
    trace_idx = {i for i, c in enumerate(conds) if c.target == T_TRACE}

    def purity(t):  # 'trace' | 'span' | 'mixed'
        if t[0] == "tracify":
            return "trace"
        if t[0] == "struct":
            # ('struct', op, lhs, rhs): spanset-relation node, span-level
            # by construction (t[1] is the op STRING -- never recurse it)
            return "span"
        if t[0] == "cond":
            return "trace" if t[1] in trace_idx else "span"
        kinds = {purity(ch) for ch in t[1:]}
        return kinds.pop() if len(kinds) == 1 else "mixed"

    def lift(t):
        p = purity(t)
        if p == "span":
            return ("tracify", t)
        if p == "trace":
            return t
        if t[0] == "and":
            # span-pure children must hold on the SAME span (single-spanset
            # semantics): group them under ONE tracify, don't lift each
            span_ch = [ch for ch in t[1:] if purity(ch) == "span"]
            rest = [lift(ch) for ch in t[1:] if purity(ch) != "span"]
            if span_ch:
                sub = span_ch[0] if len(span_ch) == 1 else ("and",) + tuple(span_ch)
                rest = [("tracify", sub)] + rest
            return rest[0] if len(rest) == 1 else ("and",) + tuple(rest)
        return (t[0],) + tuple(lift(ch) for ch in t[1:])

    return lift(tree)


@lru_cache(maxsize=256)
def _compiled(tree: CondTree | None, conds: tuple[Cond, ...], table_idxs: tuple[int, ...],
              n_spans_b: int, n_res_b: int, n_traces_b: int, span_out: bool = True):
    """tree is a TRACE-level expression: leaves are ('cond', i) with a
    trace-target cond or ('tracify', span_tree) aggregating a span-level
    subtree; None matches everything.

    span_out=False drops the span-level mask output, which lets the
    program skip the trace->span survival gather entirely (counts are
    zeroed at TRACE level instead) -- a span-length random gather is one
    of the most expensive ops on the TPU, and the search path only ever
    consumes trace-level outputs."""

    @jax.jit
    @scoped("filter")
    def run(cols, ops_i, ops_f, table_list, n_spans, n_traces):
        tables = dict(zip(table_idxs, table_list))
        valid_span = jnp.arange(n_spans_b, dtype=jnp.int32) < n_spans
        valid_trace = jnp.arange(n_traces_b, dtype=jnp.int32) < n_traces
        span_masks: list = []  # union for reporting/counts

        def ev_span(t):
            if t == ("true",):
                return valid_span
            if t == ("false",):
                return jnp.zeros_like(valid_span)
            if t[0] == "cond":
                i = t[1]
                return _cond_mask(conds[i], i, cols, ops_i, ops_f, tables,
                                  n_spans_b, n_res_b, valid_span)
            if t[0] == "struct":
                return ev_struct(t[1], ev_span(t[2]), ev_span(t[3]))
            masks = [ev_span(ch) for ch in t[1:]]
            out = masks[0]
            for m in masks[1:]:
                out = (out & m) if t[0] == "and" else (out | m)
            return out

        def ev_struct(op, lm, rm):
            """Exact structural relation over the parent-row column:
            result = rhs spans standing in `op` relation to an lhs span
            (enum_operators.go OpSpansetChild/Descendant/Sibling).
            `>` is one parent gather; `>>` is pointer-doubling (log2
            passes of gather, all fused on device); `~` is one
            segment-sum + gather."""
            pidx = cols["span.parent_idx"]
            has_p = (pidx >= 0) & valid_span
            safe = jnp.clip(pidx, 0, n_spans_b - 1)
            if op == ">":
                return rm & has_p & lm[safe]
            if op == ">>":
                # acc[i] = any lhs match among ancestors reached so far;
                # ptr doubles the jump distance every iteration
                acc = has_p & lm[safe]
                ptr = jnp.where(has_p, safe, -1)
                for _ in range(max(1, (n_spans_b - 1).bit_length())):
                    psafe = jnp.clip(ptr, 0, n_spans_b - 1)
                    alive = ptr >= 0
                    acc = acc | (alive & acc[psafe])
                    ptr = jnp.where(alive, jnp.where(ptr[psafe] >= 0, ptr[psafe], -1), -1)
                return rm & acc
            # '~': some DIFFERENT lhs span with the same parent. Orphans
            # (parent_idx == -2: parent id set but its span absent) can
            # still be siblings by shared parent ID; the row kernel can't
            # resolve that, so orphan-orphan pairs OVER-match (any lhs
            # orphan in the batch) and host verification settles them
            # (the plan flags '~' trees needs_verify).
            lhs_child = (lm & has_p).astype(jnp.int32)
            owner = jnp.where(has_p & lm, safe, n_spans_b)
            cnt = jax.ops.segment_sum(
                lhs_child, owner, num_segments=n_spans_b + 1)[:n_spans_b]
            sibs = cnt[safe] - (lm & has_p).astype(jnp.int32)
            orphan = (pidx == -2) & valid_span
            any_lhs_orphan = jnp.any(lm & orphan)
            return (rm & has_p & (sibs > 0)) | (rm & orphan & any_lhs_orphan)

        def seg_counts(span_mask):
            """Matched-span count per trace."""
            if "trace.span_off" in cols:  # grouped layout: scan + gather
                return _offset_counts(span_mask & valid_span, cols["trace.span_off"])
            sid = jnp.where(valid_span & span_mask, cols["span.trace_sid"], n_traces_b)
            sid = jnp.clip(sid, 0, n_traces_b)
            return jax.ops.segment_sum(
                span_mask.astype(jnp.int32), sid, num_segments=n_traces_b + 1
            )[:n_traces_b]

        def tracify(span_mask):
            return seg_counts(span_mask) > 0

        def ev_trace(t):
            if t[0] == "tracify":
                sm = ev_span(t[1])
                span_masks.append(sm)
                return tracify(sm)
            if t[0] == "cond":
                i = t[1]
                c = conds[i]
                return _cmp(c.op, cols[c.col], ops_i[i, 1], ops_i[i, 2],
                            ops_f[i, 0], ops_f[i, 1], c.is_float, tables.get(i))
            ms = [ev_trace(ch) for ch in t[1:]]
            out = ms[0]
            for m in ms[1:]:
                out = (out & m) if t[0] == "and" else (out | m)
            return out

        if tree is None:
            trace_mask = valid_trace
            union = valid_span
        else:
            trace_mask = ev_trace(tree) & valid_trace
            if span_masks:
                union = span_masks[0]
                for m in span_masks[1:]:
                    union = union | m
            else:
                union = valid_span

        if not span_out:
            # spans only count toward surviving traces; zero at trace
            # level -- no span-length gather needed
            span_count = jnp.where(trace_mask, seg_counts(union), 0)
            return trace_mask, span_count

        # a span only counts if its trace survived trace-level conds
        tsid = jnp.clip(cols["span.trace_sid"], 0, n_traces_b - 1)
        span_mask = union & trace_mask[tsid] & valid_span
        span_count = seg_counts(span_mask)
        return span_mask, trace_mask, span_count

    return run


def _groups_to_tree(groups) -> tuple[CondTree, tuple[Cond, ...]]:
    """CNF condition groups (tuple of OR-tuples) -> expression tree."""
    conds: list[Cond] = []
    children = []
    for g in groups:
        if isinstance(g, Cond):
            g = (g,)
        ors = []
        for c in g:
            conds.append(c)
            ors.append(("cond", len(conds) - 1))
        children.append(ors[0] if len(ors) == 1 else ("or",) + tuple(ors))
    tree = children[0] if len(children) == 1 else ("and",) + tuple(children)
    return tree, tuple(conds)


def eval_block(
    query,
    combinator_or_cols,
    *args,
    span_out: bool = True,
):
    """Two call forms:

    eval_block((tree, conds), cols, operands, n_spans, n_traces,
               n_spans_b, n_res_b, n_traces_b)               -- tree form
    eval_block(groups, "and", cols, operands, ...)            -- CNF form

    Returns (span_mask (n_spans_b,), trace_mask (n_traces_b,),
    per-trace matched span count); with span_out=False just
    (trace_mask, counts) -- cheaper on device (no span-level gather)."""
    if isinstance(combinator_or_cols, str):
        groups = query
        if combinator_or_cols != "and":
            tree, conds = _groups_to_tree([tuple(_flatten(groups))])  # single OR group
        else:
            tree, conds = _groups_to_tree(groups)
        cols, operands, n_spans, n_traces, n_spans_b, n_res_b, n_traces_b = args
    else:
        tree, conds = query
        cols = combinator_or_cols
        operands, n_spans, n_traces, n_spans_b, n_res_b, n_traces_b = args
    if tree is not None:
        tree = normalize_tree(tree, conds)  # idempotent

    from .device import bucket, pad_rows

    tables = operands.tables or {}
    table_idxs = tuple(sorted(tables))
    # host arrays/scalars go straight into the jit call: the dispatch
    # uploads them as one batch. Eager jnp conversions here would each
    # issue a separate device_put -- a blocking round trip per array on
    # a high-latency host<->device link.
    table_list = [
        pad_rows(np.asarray(tables[i], dtype=np.uint8), bucket(max(1, len(tables[i]))), 0)
        for i in table_idxs
    ]
    fn = _compiled(tree, conds, table_idxs, n_spans_b, n_res_b, n_traces_b, span_out)
    from ..util import costmodel
    from ..util.kerneltel import TEL

    ns, nt = np.int32(n_spans), np.int32(n_traces)
    with TEL.launch(
        "filter",
        ("filter", tree, conds, table_idxs, n_spans_b, n_res_b, n_traces_b, span_out,
         attr_reduce_route(conds, cols)),
        n_spans_b,
        cost=lambda: costmodel.spec(fn, cols, operands.ints, operands.floats,
                                    table_list, ns, nt),
    ) as ln:
        return ln.sync(fn(cols, operands.ints, operands.floats, table_list, ns, nt))
