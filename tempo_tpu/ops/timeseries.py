"""Time-bucketed segmented reductions: the TraceQL-metrics kernel.

One fused device pass per (block, query): evaluate the span-level
predicate tree (the same data-driven condition machinery as ops/filter,
so `{span.foo = "bar"} | rate()` and `{span.foo = "baz"} | rate()`
share a compiled program), bucketize each surviving span's start time
onto the request's step-aligned axis, and fold into
`[num_groups, num_buckets]` accumulators over a combined (group,
bucket) cell index. The accumulator's padded shape follows the request
(acc_shape), and its size alone decides how the fold runs (_fold):

- at most DENSE_MAX_CELLS cells (a panel's `rate()`, `by (service)`
  over an hour): a dense histogram -- every row compared with every
  cell and reduced over the span axis, one fused pass a statistic; no
  scatter, and no [rows, cells] array ever exists;
- above it (`by (name)` at full width): one segment reduce a statistic
  over the combined index, the same trick the span-metrics generator
  reduce uses (ops/reduce.py histogram scatter), at bucket()'s padding.

A scatter costs ~9 ns a row on a v5e however few the cells (2^24 rows:
147 ms into 65 cells as into 1,048,577), a dense fold costs by rows x
cells (the whole 1 x 64 program 3.4 ms, 4,096 cells 74): PERF.md
section 6 has the crossover.

Only the tree/condition STRUCTURE and the padded (groups, buckets)
shapes key the jit compile; operand values, group ids, value columns
and the time origin are traced, so the program is shared across blocks
and across steps/ranges of the same query shape.

Group ids arrive as a per-span int32 column computed host-side from the
by() field's dictionary codes (db/metrics_exec) -- group-key resolution
is per-block (each block has its own dictionary), the kernel only ever
sees dense ids in [0, num_groups). -1 drops the span (missing label).

Value folds (`min/avg/sum/max_over_time(field)`) take a per-span f32
value + presence mask derived host-side from the EXACT host columns
(sattr.int64/f64, span.start_ns/end_ns), so the only device-side loss
is the f32 cast -- integer counts are exact on both engines.

The host twin (eval_timeseries_host) mirrors the semantics in numpy
over raw columns (f64 accumulation) for cold blocks; exact-verify
queries bypass both engines entirely (db/metrics_exec exact path).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from .device import bucket, pad_rows, scoped
from .filter import Cond, Operands, T_TRACE, _cmp, _cond_mask, attr_reduce_route
from .hostfilter import eval_span_mask_host


# Largest accumulator (padded groups x padded buckets) the fold reduces
# densely; one more cell and it scatters. Static shape alone decides.
# At n_spans_b 2^24 on a v5e the dense count program takes 74 ms at 2^12
# cells and 530 at 2^14 against the scatter's 149; with a value fold 340
# at 2^12 against 731 (PERF.md section 6, PR 32).
DENSE_MAX_CELLS = 1 << 12
_DENSE_MIN_BUCKETS = 64  # a panel's hour at step 60 s is 60-61 buckets


def fold_route(n_cells: int) -> tuple[str, str]:
    """(engine, reason) of the `ts_fold` routing row: how _fold reduces
    into a padded accumulator of `n_cells`."""
    if n_cells <= DENSE_MAX_CELLS:
        return "dense", "small_acc"
    return "scatter", "large_acc"


def acc_shape(n_groups: int, n_buckets: int) -> tuple[int, int]:
    """Padded (G_b, B_b) of a request's accumulator -- what keys the
    compile, what db/metrics_exec._check_cardinality caps. Powers of two
    from 1 x 64 while the fold stays dense; past that bucket()'s
    padding, the shapes the scatter programs always had."""
    g = 1 << max(n_groups - 1, 0).bit_length()
    b = max(_DENSE_MIN_BUCKETS, 1 << max(n_buckets - 1, 0).bit_length())
    if fold_route(g * b)[0] == "dense":
        return g, b
    return bucket(n_groups), bucket(n_buckets)


_FOLDS = {"sum": (jnp.sum, jax.ops.segment_sum, 0),
          "min": (jnp.min, jax.ops.segment_min, np.inf),
          "max": (jnp.max, jax.ops.segment_max, -np.inf)}


def _fold(weights, seg, n_cells: int, kind: str):
    """Reduce `weights` (sum / min / max) into `n_cells` cells by `seg`;
    a row whose seg is n_cells lands nowhere. Small accumulators compare
    every row with every cell and reduce over the span axis -- the
    compiler fuses compare, select and reduce, so no [rows, cells] array
    exists; large ones scatter."""
    dense, scatter, identity = _FOLDS[kind]
    if fold_route(n_cells)[0] == "scatter":
        return scatter(weights, seg, num_segments=n_cells + 1)[:-1]
    cells = jnp.arange(n_cells, dtype=jnp.int32)
    hit = seg[None, :] == cells[:, None]
    return dense(jnp.where(hit, weights[None, :],
                           jnp.asarray(identity, weights.dtype)), axis=1)


@lru_cache(maxsize=256)
def _compiled_ts(tree, conds: tuple[Cond, ...], table_idxs: tuple[int, ...],
                 has_val: bool, n_spans_b: int, n_res_b: int, n_traces_b: int,
                 G_b: int, B_b: int):
    """tree: raw SPAN-level expression (no tracify); None matches all.
    Trace-target conds gather through span.trace_sid."""

    @jax.jit
    @scoped("timeseries")
    def run(cols, ops_i, ops_f, table_list, gid, val, vpres,
            t0_ms, step_ms, n_spans, n_buckets):
        tables = dict(zip(table_idxs, table_list))
        valid = jnp.arange(n_spans_b, dtype=jnp.int32) < n_spans

        def ev(t):
            if t == ("true",):
                return valid
            if t == ("false",):
                return jnp.zeros_like(valid)
            if t[0] == "cond":
                i = t[1]
                c = conds[i]
                if c.target == T_TRACE:
                    tm = _cmp(c.op, cols[c.col], ops_i[i, 1], ops_i[i, 2],
                              ops_f[i, 0], ops_f[i, 1], c.is_float,
                              tables.get(i))
                    sid = jnp.clip(cols["span.trace_sid"], 0, n_traces_b - 1)
                    return tm[sid] & valid
                return _cond_mask(c, i, cols, ops_i, ops_f, tables,
                                  n_spans_b, n_res_b, valid)
            ms = [ev(ch) for ch in t[1:]]
            out = ms[0]
            for m in ms[1:]:
                out = (out & m) if t[0] == "and" else (out | m)
            return out

        sm = valid if tree is None else (ev(tree) & valid)
        # int32 bucket math (x64 stays off): the caller clips t0 into
        # int32, and blocks span hours, not the ~24-day int32-ms range
        b = (cols["span.start_ms"] - t0_ms) // step_ms
        ok = sm & (b >= 0) & (b < n_buckets) & (gid >= 0)
        b32 = jnp.clip(b, 0, B_b - 1).astype(jnp.int32)
        n_cells = G_b * B_b
        seg = jnp.where(ok, gid * B_b + b32, n_cells)

        def fold(weights, segs, kind):
            return _fold(weights, segs, n_cells, kind).reshape(G_b, B_b)

        counts = fold(ok.astype(jnp.int32), seg, "sum")
        if not has_val:
            return (counts,)
        pres = ok & vpres
        segv = jnp.where(pres, seg, n_cells)
        vcnt = fold(pres.astype(jnp.int32), segv, "sum")
        vsum = fold(jnp.where(pres, val, jnp.float32(0)), segv, "sum")
        vmin = fold(jnp.where(pres, val, jnp.float32(jnp.inf)), segv, "min")
        vmax = fold(jnp.where(pres, val, jnp.float32(-jnp.inf)), segv, "max")
        return counts, vcnt, vsum, vmin, vmax

    return run


def _table_list(operands: Operands):
    tables = operands.tables or {}
    table_idxs = tuple(sorted(tables))
    return table_idxs, [
        pad_rows(np.asarray(tables[i], dtype=np.uint8),
                 bucket(max(1, len(tables[i]))), 0)
        for i in table_idxs
    ]


def eval_timeseries_device(query, staged, operands: Operands,
                           gid: np.ndarray, val: np.ndarray | None,
                           vpres: np.ndarray | None,
                           t0_rel_ms: int, step_ms: int,
                           n_buckets: int, n_groups: int):
    """One fused device dispatch over a StagedBlock (ops/stage).
    gid/val/vpres are raw span-length host arrays for the staged span
    slice; the padded uploads ride the jit call's batched transfer.
    Returns numpy accumulators clipped to (n_groups, n_buckets):
    (counts,) or (counts, vcnt, vsum, vmin, vmax)."""
    tree, conds = query
    G_b, B_b = acc_shape(n_groups, n_buckets)
    table_idxs, tabs = _table_list(operands)
    has_val = val is not None
    fn = _compiled_ts(tree, conds, table_idxs, has_val,
                      staged.n_spans_b, staged.n_res_b, staged.n_traces_b,
                      G_b, B_b)
    gid_p = pad_rows(np.asarray(gid, np.int32), staged.n_spans_b, np.int32(-1))
    if has_val:
        val_p = pad_rows(np.asarray(val, np.float32), staged.n_spans_b,
                         np.float32(0))
        pres_p = pad_rows(np.asarray(vpres, bool), staged.n_spans_b, False)
    else:
        val_p = pres_p = np.zeros(0, np.float32)
    t0 = int(np.clip(t0_rel_ms, -(2**31) + 1, 2**31 - 1))

    from ..util import costmodel
    from ..util.kerneltel import TEL

    t0_i = np.int32(t0)
    step_i = np.int32(max(1, step_ms))
    ns_i, nb_i = np.int32(staged.n_spans), np.int32(n_buckets)
    route = fold_route(G_b * B_b)
    TEL.record_routing("ts_fold", *route)
    with TEL.launch(
        "timeseries",
        ("ts", tree, conds, table_idxs, has_val, staged.n_spans_b,
         staged.n_res_b, staged.n_traces_b, G_b, B_b, route,
         attr_reduce_route(conds, staged.cols)),
        staged.n_spans_b,
        cost=lambda: costmodel.spec(fn, staged.cols, operands.ints,
                                    operands.floats, tabs, gid_p, val_p,
                                    pres_p, t0_i, step_i, ns_i, nb_i),
    ):
        outs = fn(staged.cols, operands.ints, operands.floats, tabs,
                  gid_p, val_p, pres_p,
                  t0_i, step_i, ns_i, nb_i)
        res = tuple(np.asarray(o)[:n_groups, :n_buckets] for o in outs)
    return res


def eval_timeseries_host(query, cols: dict[str, np.ndarray],
                         operands: Operands, n_spans: int, n_traces: int,
                         gid: np.ndarray, val: np.ndarray | None,
                         vpres: np.ndarray | None,
                         t0_rel_ms: int, step_ms: int,
                         n_buckets: int, n_groups: int):
    """Numpy twin of the device kernel over RAW host columns (the cold-
    block engine): same masks, same bucketing, f64 value accumulation.
    Returns the same accumulator tuple shapes as the device path."""
    sm = eval_span_mask_host(query, cols, operands, n_spans, n_traces)
    b = (cols["span.start_ms"].astype(np.int64) - int(t0_rel_ms)) // int(step_ms)
    ok = sm & (b >= 0) & (b < n_buckets) & (gid >= 0)
    nb = int(n_buckets)
    key = gid.astype(np.int64) * nb + np.clip(b, 0, nb - 1)
    nk = max(n_groups, 1) * nb
    counts = np.bincount(key[ok], minlength=nk)[:nk].reshape(-1, nb)
    counts = counts[:n_groups]
    if val is None:
        return (counts,)
    pres = ok & vpres
    kp = key[pres]
    vcnt = np.bincount(kp, minlength=nk)[:nk].reshape(-1, nb)[:n_groups]
    vv = val.astype(np.float64)[pres]
    vsum = np.bincount(kp, weights=vv, minlength=nk)[:nk].reshape(-1, nb)[:n_groups]
    vmin = np.full(nk, np.inf)
    vmax = np.full(nk, -np.inf)
    np.minimum.at(vmin, kp, vv)
    np.maximum.at(vmax, kp, vv)
    return (counts, vcnt, vsum,
            vmin.reshape(-1, nb)[:n_groups], vmax.reshape(-1, nb)[:n_groups])
