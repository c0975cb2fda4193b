"""Multi-query predicate programs: Q queries x R rows in ONE launch.

The single-query engine (ops/filter.py) compiles one XLA program per
condition-tree STRUCTURE; under concurrency the device therefore runs Q
small launches over the same staged block -- Q dispatch round trips and
Q trace-through-jit risks for work the VPU could do in one pass. This
module is the kernel half of the cross-query batching executor
(db/batchexec.py), the serving-stack analog of continuous batching in
inference servers (Orca, OSDI '22): concurrent queries merge into one
device step.

Lowering (`lower_plan`) turns a planned query's condition tree into a
fixed-shape *predicate program*:

  * span-level conditions become padded (column-id, op-code, operand)
    tables -- data, not structure, so they ride the traced-operand path;
  * the boolean tree flattens to CNF at two levels: span conds group
    into OR-clauses under AND per tracify group (same-span semantics
    preserved), and trace-level atoms (tracify-group results + trace
    conds) group into OR-clauses under AND;
  * every table pads to a power-of-two bucket (ProgramShape), so the
    launch key depends only on the shape buckets + column set -- never
    on which queries happen to share a window.

Evaluation (`eval_multiquery`) vmaps the program interpreter over the
query axis: one fused filter -> clause-fold -> segmented-fold kernel
produces per-query (trace_mask, matched-span counts), bit-identical to
running ops/filter.eval_block per query (CNF is a boolean identity and
every aggregation reuses the same cumsum+gather segment fold).
`select_multiquery` then runs ONE batched top-k over all Q mask rows --
two launches total for the whole window, vs 2Q sequentially.

Eligibility is conservative: conditions over dedicated int32 columns
(span/trace intrinsics, well-known res/span attrs via the span@
materialization) with scalar compare ops. Regex tables, generic attr
tables, struct relations and float compares return None from
`lower_plan`; the caller falls back to the single-query path unchanged.
Per-query `needs_verify` semantics are untouched -- exact host
re-verification happens after demux, per query.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from .device import PAD_I32, scoped
from .filter import Cond, T_RES, T_SPAN, T_TRACE, normalize_tree

# op codes (order matters: _cmp_code dispatches on these)
_OPC = {"eq": 0, "ne": 1, "ne_present": 2, "lt": 3, "le": 4,
        "gt": 5, "ge": 6, "range": 7}
_NOP = -1  # padded condition slot: mask is False everywhere

# per-query program-size ceilings; a query that lowers past any of them
# is ineligible (falls back to the single-query engine)
MAX_CONDS = 32
MAX_CLAUSES = 16
MAX_GROUPS = 8
MAX_TCONDS = 16
MAX_ATOMS = 16
MAX_TCLAUSES = 8


def _p2(n: int, lo: int = 2) -> int:
    """Small power-of-two bucket (program tables, not row axes)."""
    b = lo
    while b < n:
        b <<= 1
    return b


@dataclass(frozen=True)
class ProgramShape:
    """Bucketed program dims + column set: the plan-signature half of
    the coalesce key, and (with the axis buckets) the launch key."""

    n_conds_b: int
    n_clauses_b: int
    n_groups_b: int
    n_tconds_b: int
    n_atoms_b: int
    n_tclauses_b: int
    span_cols: tuple[str, ...]  # staged span-axis columns, indexed by cond_col
    trace_cols: tuple[str, ...]  # staged trace-axis columns, by tcond_col


@dataclass
class LoweredQuery:
    """One query's predicate program (host-side numpy tables, padded to
    the ProgramShape buckets)."""

    shape: ProgramShape
    # span-level conds, sorted by (group, clause); padded slots op=_NOP
    cond_col: np.ndarray  # (P,) index into shape.span_cols
    cond_op: np.ndarray  # (P,)
    cond_v0: np.ndarray  # (P,)
    cond_v1: np.ndarray  # (P,)
    cond_guard: np.ndarray  # (P,) bool: require x != PAD (span@res cols)
    clause_off: np.ndarray  # (NC+1,) cond-slot boundaries per clause
    group_off: np.ndarray  # (NG+1,) clause boundaries per tracify group
    n_groups: int
    # trace-level conds + atoms, atoms sorted by trace clause
    tcond_col: np.ndarray  # (PT,)
    tcond_op: np.ndarray  # (PT,)
    tcond_v0: np.ndarray  # (PT,)
    tcond_v1: np.ndarray  # (PT,)
    atom_kind: np.ndarray  # (NA,) 0=group result, 1=trace cond, -1=pad
    atom_idx: np.ndarray  # (NA,)
    tclause_off: np.ndarray  # (TC+1,) atom boundaries per trace clause
    n_tclauses: int


# --------------------------------------------------------------- lowering


def _cnf(tree, clause_cap: int = MAX_CLAUSES):
    """and/or tree with hashable leaves -> list of OR-clauses (lists of
    leaves) whose AND is equivalent. None when distribution would exceed
    clause_cap (OR-of-AND blowup)."""
    if not isinstance(tree, tuple) or tree[0] not in ("and", "or"):
        return [[tree]]
    parts = [_cnf(ch, clause_cap) for ch in tree[1:]]
    if any(p is None for p in parts):
        return None
    if tree[0] == "and":
        out = [c for p in parts for c in p]
        return out if len(out) <= clause_cap else None
    # or: cross-product of the children's clause sets
    out = [[]]
    for p in parts:
        nxt = []
        for acc in out:
            for clause in p:
                nxt.append(acc + clause)
                if len(nxt) > clause_cap:
                    return None
        out = nxt
    return out


def lower_plan(planned) -> LoweredQuery | None:
    """PlannedQuery (traceql/plan.py) -> predicate program, or None when
    the plan can't be expressed in the fixed-shape op set (caller falls
    back to the single-query engine). Must be given a non-pruned plan."""
    conds = tuple(planned.conds)
    if planned.tables:  # regex / set tables: per-query table shapes
        return None
    if getattr(planned, "has_struct", False):
        return None
    for c in conds:
        if c.target not in (T_SPAN, T_TRACE, T_RES):
            return None  # generic attr tables (sattr/rattr)
        if c.op not in _OPC or c.is_float:
            return None
    tree = planned.tree
    rows = planned.rows

    # trace-level tree -> atoms (tracify groups + trace conds)
    groups: list[list[list[int]]] = []  # per group: clauses of cond idxs
    atoms: list[tuple[int, int]] = []  # (kind, idx)
    tcond_idx: list[int] = []  # cond indices used at trace level

    def span_leaf(t):
        """span-CNF leaf check: ('cond', i) with span/res target."""
        return (isinstance(t, tuple) and len(t) == 2 and t[0] == "cond"
                and conds[t[1]].target in (T_SPAN, T_RES))

    def lower_tracify(span_tree) -> int | None:
        """span subtree -> group id (appended), or None if unlowerable."""
        if span_tree == ("true",):
            clauses: list[list[int]] | None = []  # AND of nothing: all spans
        elif span_tree == ("false",):
            return None  # planner folds these away; don't guess
        else:
            clauses = _cnf(span_tree)
            if clauses is None or len(clauses) > MAX_CLAUSES:
                return None
            for cl in clauses:
                for leaf in cl:
                    if not span_leaf(leaf):
                        return None
        groups.append([[leaf[1] for leaf in cl] for cl in (clauses or [])])
        return len(groups) - 1

    if tree is not None:
        tree = normalize_tree(tree, conds)
        tcnf = _cnf(tree, MAX_TCLAUSES)
        if tcnf is None or len(tcnf) > MAX_TCLAUSES:
            return None
        tclauses: list[list[int]] = []  # per trace clause: atom ids
        for cl in tcnf:
            atom_ids = []
            for leaf in cl:
                if isinstance(leaf, tuple) and leaf[0] == "tracify":
                    g = lower_tracify(leaf[1])
                    if g is None:
                        return None
                    atoms.append((0, g))
                elif isinstance(leaf, tuple) and leaf[0] == "cond" \
                        and conds[leaf[1]].target == T_TRACE:
                    tcond_idx.append(leaf[1])
                    atoms.append((1, len(tcond_idx) - 1))
                else:
                    return None  # struct / constants inside a clause
                atom_ids.append(len(atoms) - 1)
            tclauses.append(atom_ids)
    else:
        tclauses = []

    n_sconds = sum(len(cl) for g in groups for cl in g)
    n_clauses = sum(len(g) for g in groups)
    if (n_sconds > MAX_CONDS or n_clauses > MAX_CLAUSES
            or len(groups) > MAX_GROUPS or len(tcond_idx) > MAX_TCONDS
            or len(atoms) > MAX_ATOMS or len(tclauses) > MAX_TCLAUSES):
        return None

    # column maps (sorted for a canonical signature)
    span_cols = sorted({
        (f"span@{conds[i].col}" if conds[i].target == T_RES else conds[i].col)
        for g in groups for cl in g for i in cl
    })
    trace_cols = sorted({conds[i].col for i in tcond_idx})
    scol_of = {n: j for j, n in enumerate(span_cols)}
    tcol_of = {n: j for j, n in enumerate(trace_cols)}

    shape = ProgramShape(
        n_conds_b=_p2(max(n_sconds, 1)),
        n_clauses_b=_p2(max(n_clauses, 1)),
        n_groups_b=_p2(max(len(groups), 1), lo=1),
        n_tconds_b=_p2(max(len(tcond_idx), 1), lo=1),
        n_atoms_b=_p2(max(len(atoms), 1), lo=1),
        n_tclauses_b=_p2(max(len(tclauses), 1), lo=1),
        span_cols=tuple(span_cols),
        trace_cols=tuple(trace_cols),
    )

    def v01(i):
        v0 = int(np.clip(rows[i][1], -(2**31), 2**31 - 1))
        v1 = int(np.clip(rows[i][2], -(2**31), 2**31 - 1))
        return v0, v1

    P, NC, NG = shape.n_conds_b, shape.n_clauses_b, shape.n_groups_b
    PT, NA, TC = shape.n_tconds_b, shape.n_atoms_b, shape.n_tclauses_b
    cond_col = np.zeros(P, np.int32)
    cond_op = np.full(P, _NOP, np.int32)
    cond_v0 = np.zeros(P, np.int32)
    cond_v1 = np.zeros(P, np.int32)
    cond_guard = np.zeros(P, bool)
    clause_off = np.zeros(NC + 1, np.int32)
    group_off = np.zeros(NG + 1, np.int32)
    s = c_i = 0
    for gi, g in enumerate(groups):
        group_off[gi] = c_i
        for cl in g:
            clause_off[c_i] = s
            for i in cl:
                c = conds[i]
                name = f"span@{c.col}" if c.target == T_RES else c.col
                cond_col[s] = scol_of[name]
                cond_op[s] = _OPC[c.op]
                cond_v0[s], cond_v1[s] = v01(i)
                cond_guard[s] = c.target == T_RES
                s += 1
            c_i += 1
            clause_off[c_i] = s
    group_off[len(groups):] = c_i
    clause_off[c_i:] = s  # padded clauses: empty ranges past the real conds

    tcond_col = np.zeros(PT, np.int32)
    tcond_op = np.full(PT, _NOP, np.int32)
    tcond_v0 = np.zeros(PT, np.int32)
    tcond_v1 = np.zeros(PT, np.int32)
    for j, i in enumerate(tcond_idx):
        tcond_col[j] = tcol_of[conds[i].col]
        tcond_op[j] = _OPC[conds[i].op]
        tcond_v0[j], tcond_v1[j] = v01(i)

    atom_kind = np.full(NA, _NOP, np.int32)
    atom_idx = np.zeros(NA, np.int32)
    tclause_off = np.zeros(TC + 1, np.int32)
    a = 0
    for ti, atom_ids in enumerate(tclauses):
        tclause_off[ti] = a
        for aid in atom_ids:
            atom_kind[a], atom_idx[a] = atoms[aid]
            a += 1
        tclause_off[ti + 1] = a
    tclause_off[len(tclauses):] = a

    return LoweredQuery(
        shape=shape,
        cond_col=cond_col, cond_op=cond_op, cond_v0=cond_v0, cond_v1=cond_v1,
        cond_guard=cond_guard, clause_off=clause_off, group_off=group_off,
        n_groups=len(groups),
        tcond_col=tcond_col, tcond_op=tcond_op,
        tcond_v0=tcond_v0, tcond_v1=tcond_v1,
        atom_kind=atom_kind, atom_idx=atom_idx, tclause_off=tclause_off,
        n_tclauses=len(tclauses),
    )


def pack_queries(lowered: list[LoweredQuery], q_b: int) -> dict[str, np.ndarray]:
    """Stack Q programs (identical ProgramShape) into (q_b, ...) tables;
    padded query rows match nothing (one impossible trace clause)."""
    shape = lowered[0].shape
    out: dict[str, np.ndarray] = {}
    fields = ("cond_col", "cond_op", "cond_v0", "cond_v1", "cond_guard",
              "clause_off", "group_off", "tcond_col", "tcond_op",
              "tcond_v0", "tcond_v1", "atom_kind", "atom_idx", "tclause_off")
    for f in fields:
        out[f] = np.stack([getattr(lq, f) for lq in lowered]
                          + [np.zeros_like(getattr(lowered[0], f))]
                          * (q_b - len(lowered)))
    ng = np.asarray([lq.n_groups for lq in lowered]
                    + [0] * (q_b - len(lowered)), np.int32)
    # padded queries: one empty trace clause => OR over nothing => False
    ntc = np.asarray([lq.n_tclauses for lq in lowered]
                     + [1] * (q_b - len(lowered)), np.int32)
    out["n_groups"] = ng
    out["n_tclauses"] = ntc
    assert all(lq.shape == shape for lq in lowered)
    return out


# ----------------------------------------------------------------- kernel


def _cmp_code(opc, x, v0, v1):
    """Data-driven compare: op code is a traced array, so one compiled
    program serves every operand mix. Padded slots (opc == _NOP) and
    unknown codes yield False."""
    return (
        ((opc == 0) & (x == v0))
        | ((opc == 1) & (x != v0))
        | ((opc == 2) & ((x != v0) & (x >= 0)))
        | ((opc == 3) & (x < v0))
        | ((opc == 4) & (x <= v0))
        | ((opc == 5) & (x > v0))
        | ((opc == 6) & (x >= v0))
        | ((opc == 7) & ((x >= v0) & (x <= v1)))
    )


@lru_cache(maxsize=64)
def _compiled_multiquery(shape: ProgramShape, q_b: int, n_spans_b: int,
                         n_traces_b: int):
    n_sc = max(1, len(shape.span_cols))
    n_tc = max(1, len(shape.trace_cols))

    @jax.jit
    @scoped("multiquery")
    def run(span_cols, trace_cols, span_off, progs, n_spans, n_traces):
        valid_span = jnp.arange(n_spans_b, dtype=jnp.int32) < n_spans
        valid_trace = jnp.arange(n_traces_b, dtype=jnp.int32) < n_traces
        span_mat = (jnp.stack(span_cols) if span_cols
                    else jnp.zeros((1, n_spans_b), jnp.int32))
        trace_mat = (jnp.stack(trace_cols) if trace_cols
                     else jnp.zeros((1, n_traces_b), jnp.int32))

        def one(p):
            # span conds -> (P, S) masks
            x = span_mat[jnp.clip(p["cond_col"], 0, n_sc - 1)]
            m = _cmp_code(p["cond_op"][:, None], x,
                          p["cond_v0"][:, None], p["cond_v1"][:, None])
            m = m & (~p["cond_guard"][:, None] | (x != PAD_I32))
            m = m & valid_span[None, :]
            # OR within clauses: cumsum along the cond axis + boundary
            # gathers (the same scan-not-scatter fold as ops/filter)
            cs = jnp.concatenate(
                [jnp.zeros((1, n_spans_b), jnp.int32),
                 jnp.cumsum(m.astype(jnp.int32), axis=0)])
            co = p["clause_off"]
            clause_ok = (cs[co[1:]] - cs[co[:-1]]) > 0  # (NC, S)
            # AND across a group's clauses: count == clause count
            cs2 = jnp.concatenate(
                [jnp.zeros((1, n_spans_b), jnp.int32),
                 jnp.cumsum(clause_ok.astype(jnp.int32), axis=0)])
            go = p["group_off"]
            n_cl = (go[1:] - go[:-1])[:, None]
            grp_ok = ((cs2[go[1:]] - cs2[go[:-1]]) == n_cl) & valid_span[None, :]
            # per-group per-trace matched counts (grouped span layout)
            cs3 = jnp.concatenate(
                [jnp.zeros((grp_ok.shape[0], 1), jnp.int32),
                 jnp.cumsum(grp_ok.astype(jnp.int32), axis=1)], axis=1)
            gcounts = cs3[:, span_off[1:]] - cs3[:, span_off[:-1]]  # (NG, T)
            gmask = gcounts > 0
            # trace conds
            tx = trace_mat[jnp.clip(p["tcond_col"], 0, n_tc - 1)]
            tcm = _cmp_code(p["tcond_op"][:, None], tx,
                            p["tcond_v0"][:, None], p["tcond_v1"][:, None])
            # atoms -> trace clauses -> AND
            kind = p["atom_kind"]
            aval = jnp.where(
                (kind == 0)[:, None],
                gmask[jnp.clip(p["atom_idx"], 0, gmask.shape[0] - 1)],
                tcm[jnp.clip(p["atom_idx"], 0, tcm.shape[0] - 1)],
            ) & (kind >= 0)[:, None]
            cs4 = jnp.concatenate(
                [jnp.zeros((1, n_traces_b), jnp.int32),
                 jnp.cumsum(aval.astype(jnp.int32), axis=0)])
            to = p["tclause_off"]
            tcl_ok = ((cs4[to[1:]] - cs4[to[:-1]]) > 0) | (
                jnp.arange(to.shape[0] - 1) >= p["n_tclauses"])[:, None]
            tm = jnp.all(tcl_ok, axis=0) & valid_trace
            # union of group span masks = the reporting mask; no groups
            # (pure trace conds / match-all) counts every valid span
            live = (jnp.arange(grp_ok.shape[0]) < p["n_groups"])[:, None]
            union = jnp.where(p["n_groups"] > 0,
                              jnp.any(grp_ok & live, axis=0), valid_span)
            ucs = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(union.astype(jnp.int32))])
            counts = jnp.where(tm, ucs[span_off[1:]] - ucs[span_off[:-1]], 0)
            return tm, counts

        return jax.vmap(one)(progs)

    return run


def mq_bytes_estimate(shape: ProgramShape, q_b: int, n_spans_b: int) -> int:
    """Dominant intermediate footprint of one fused launch (the (Q, P,
    S) cond masks + cumsums in int32); the executor budget-gates on it."""
    return q_b * max(1, shape.n_conds_b) * n_spans_b * 4 * 3


def eval_multiquery(lowered: list[LoweredQuery], staged, progs: dict):
    """Run Q packed programs against one staged block: ONE fused launch.
    Returns device (q_b, n_traces_b) trace_mask, counts."""

    from ..util.kerneltel import TEL

    shape = lowered[0].shape
    q_b = progs["cond_op"].shape[0]
    fn = _compiled_multiquery(shape, q_b, staged.n_spans_b, staged.n_traces_b)
    span_cols = tuple(staged.cols[n] for n in shape.span_cols)
    trace_cols = tuple(staged.cols[n] for n in shape.trace_cols)
    with TEL.launch(
        "multiquery",
        ("mq", shape, q_b, staged.n_spans_b, staged.n_traces_b),
        staged.n_spans_b,
    ) as ln:
        return ln.sync(fn(
            span_cols, trace_cols, staged.cols["trace.span_off"],
            progs, np.int32(staged.n_spans), np.int32(staged.n_traces)))


_NEG = -(2**31)


@lru_cache(maxsize=64)
def _compiled_mq_select(k: int, q_b: int):
    @jax.jit
    @scoped("mq_select")
    def sel(tm, key, counts):
        keyed = jnp.where(tm, key.astype(jnp.int32)[None, :], jnp.int32(_NEG))
        _, topi = jax.lax.top_k(keyed, k)  # (Q, k), rowwise == 1-D top_k
        valid = jnp.take_along_axis(tm, topi, axis=1).astype(jnp.int32)
        cnt = jnp.take_along_axis(counts, topi, axis=1)
        nm = jnp.sum(tm.astype(jnp.int32), axis=1)
        return jnp.concatenate(
            [topi.astype(jnp.int32), cnt, valid, nm[:, None]], axis=1)

    return sel


def select_multiquery(tm, key, counts, k: int):
    """Batched twin of ops/select.select_topk_device: one launch + one
    fetch for all Q queries. Returns per query the RAW (sids, counts,
    valid, n_match) arrays of length k, still in top-k order -- callers
    slice to their own smaller k' THEN apply valid, which reproduces the
    single-query select at k' exactly (top_k's order is deterministic,
    so the first k' slots of a k-select equal a k'-select)."""

    from ..util.kerneltel import TEL

    q_b, nt = int(tm.shape[0]), int(tm.shape[1])
    k = int(min(k, nt))
    with TEL.launch("mq_select", ("mqsel", k, q_b, nt), k):
        out = np.asarray(_compiled_mq_select(k, q_b)(tm, key, counts))
    res = []
    for q in range(q_b):
        row = out[q]
        res.append((row[:k], row[k:2 * k], row[2 * k:3 * k] > 0,
                    int(row[3 * k])))
    return res
