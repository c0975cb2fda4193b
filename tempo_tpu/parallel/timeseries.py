"""Sharded metrics fold: blocks over a 1-D 'dp' axis, combined by psum.

The multi-chip twin of ops/timeseries: stacked per-block columns shard
over 'dp' (each chip folds its slice of blocks with the same fused
filter->bucketize->segmented-fold), and the [num_groups, num_buckets]
partial accumulators combine with ONE collective -- `psum` for counts
and sums, `pmin`/`pmax` for the min/max folds. Group ids arrive already
GLOBALIZED (db/metrics_mesh unions the per-block label sets and remaps
each block's dense ids onto the global table), which is exactly what
makes the cross-chip psum correct: every chip accumulates into the same
group axis.

Operands are per block (each block's dictionary yields different codes
for the same query), carried with a leading block axis like
parallel/search. Cond targets cover the span/res/trace axes; generic
attr conds take the per-block fallback path instead (db/metrics_exec) --
they need the attr-table machinery, and a metrics query hot enough to
matter runs on dedicated res/span columns.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..ops.filter import Cond, T_RES, T_SPAN, T_TRACE
from ..ops.device import scoped
from .mesh import smap
from .search import _cmp_b, _stack_operands

MESH_TARGETS = (T_SPAN, T_RES, T_TRACE)


def mesh_1d(mesh) -> Mesh:
    """Flatten a (dp, sp) query mesh into the 1-D block axis this fold
    shards over (every chip folds whole blocks; rows are not split)."""
    return Mesh(mesh.devices.reshape(-1), ("dp",))


@lru_cache(maxsize=64)
def make_sharded_timeseries(mesh, tree, conds: tuple[Cond, ...],
                            col_names: tuple[str, ...], has_val: bool,
                            G_b: int, NB_b: int, NT: int,
                            table_idxs: tuple[int, ...] = ()):
    nseg = G_b * NB_b + 1

    def local(ops_i, ops_f, n_spans_l, t0_l, step, n_buckets, gid, val, pres,
              *arrays):
        n_tab = len(table_idxs)
        tables = dict(zip(table_idxs, arrays[:n_tab]))
        cols = dict(zip(col_names, arrays[n_tab:]))
        Sl = cols["span.start_ms"].shape[1]
        valid = (jnp.arange(Sl, dtype=jnp.int32)[None, :]
                 < n_spans_l[:, None])

        def cond_cmp(i, x):
            c = conds[i]
            return _cmp_b(c.op, x, ops_i[:, i, 1], ops_i[:, i, 2],
                          ops_f[:, i, 0], ops_f[:, i, 1], c.is_float,
                          tables.get(i))

        def cond_mask(i):
            c = conds[i]
            if c.target == T_SPAN:
                return cond_cmp(i, cols[c.col]) & valid
            if c.target == T_RES:
                rm = cond_cmp(i, cols[c.col])  # (Bl, R)
                idx = jnp.clip(cols["span.res_idx"], 0, rm.shape[1] - 1)
                rm_g = jnp.take_along_axis(rm, idx, axis=1)
                return rm_g & (cols["span.res_idx"] >= 0) & valid
            if c.target == T_TRACE:
                tm = cond_cmp(i, cols[c.col])  # (Bl, NT)
                sid = jnp.clip(cols["span.trace_sid"], 0, NT - 1)
                return jnp.take_along_axis(tm, sid, axis=1) & valid
            raise ValueError(f"mesh timeseries: unsupported target {c.target}")

        def ev(t):
            if t == ("true",):
                return valid
            if t == ("false",):
                return jnp.zeros_like(valid)
            if t[0] == "cond":
                return cond_mask(t[1])
            ms = [ev(ch) for ch in t[1:]]
            out = ms[0]
            for m in ms[1:]:
                out = (out & m) if t[0] == "and" else (out | m)
            return out

        sm = valid if tree is None else (ev(tree) & valid)
        b = (cols["span.start_ms"] - t0_l[:, None]) // step
        ok = sm & (b >= 0) & (b < n_buckets) & (gid >= 0)
        b32 = jnp.clip(b, 0, NB_b - 1)
        seg = jnp.where(ok, gid * NB_b + b32, G_b * NB_b)

        def fold_sum(weights, segs):
            per_block = jax.vmap(
                lambda w, s: jax.ops.segment_sum(w, s, num_segments=nseg)[:-1]
            )(weights, segs)
            return jax.lax.psum(per_block.sum(axis=0), "dp").reshape(G_b, NB_b)

        counts = fold_sum(ok.astype(jnp.int32), seg)
        if not has_val:
            return (counts,)
        pres2 = ok & pres
        segv = jnp.where(pres2, seg, G_b * NB_b)
        vcnt = fold_sum(pres2.astype(jnp.int32), segv)
        vsum = fold_sum(jnp.where(pres2, val, jnp.float32(0)), segv)
        vmin = jax.lax.pmin(jax.vmap(
            lambda w, s: jax.ops.segment_min(w, s, num_segments=nseg)[:-1]
        )(jnp.where(pres2, val, jnp.float32(jnp.inf)), segv).min(axis=0),
            "dp").reshape(G_b, NB_b)
        vmax = jax.lax.pmax(jax.vmap(
            lambda w, s: jax.ops.segment_max(w, s, num_segments=nseg)[:-1]
        )(jnp.where(pres2, val, jnp.float32(-jnp.inf)), segv).max(axis=0),
            "dp").reshape(G_b, NB_b)
        return counts, vcnt, vsum, vmin, vmax

    n_in = 9 + len(table_idxs) + len(col_names)
    in_specs = [P("dp"), P("dp"), P("dp"), P("dp"), P(), P(),
                P("dp"), P("dp"), P("dp")]
    in_specs += [P("dp")] * (len(table_idxs) + len(col_names))
    assert len(in_specs) == n_in
    n_out = 5 if has_val else 1
    fn = smap(scoped("mesh_timeseries")(local), mesh, in_specs=tuple(in_specs),
              out_specs=tuple([P()] * n_out) if n_out > 1 else (P(),))
    return jax.jit(fn)


def sharded_timeseries(mesh, tree, conds, operands, cols: dict[str, np.ndarray],
                       n_spans: np.ndarray, t0_rel: np.ndarray,
                       gid: np.ndarray, val: np.ndarray | None,
                       pres: np.ndarray | None,
                       step_ms: int, n_buckets: int, n_groups: int):
    """Host entry. cols: stacked/padded per-block arrays -- span axis
    (B, S), res axis (B, R), trace axis (B, NT); B a multiple of the
    device count. gid: (B, S) GLOBAL dense group ids (-1 drops). val /
    pres: (B, S) f32/bool or None for count folds. t0_rel: (B,) per-
    block request-origin offset in block-relative ms. Returns numpy
    accumulators clipped to (n_groups, n_buckets)."""
    from ..ops.device import bucket

    m1 = mesh_1d(mesh)
    names = tuple(sorted(cols))
    B, S = cols["span.start_ms"].shape
    NT = next((cols[n].shape[1] for n in names if n.startswith("trace.")), 1)
    conds = tuple(conds)
    ints, floats, tabs = _stack_operands(operands, B, len(conds))
    table_idxs = tuple(sorted(tabs))
    G_b, NB_b = bucket(max(n_groups, 1)), bucket(max(n_buckets, 1))
    has_val = val is not None
    fn = make_sharded_timeseries(m1, tree, conds, names, has_val,
                                 G_b, NB_b, NT, table_idxs)
    if not has_val:
        val = np.zeros((B, 1), np.float32)
        pres = np.zeros((B, 1), bool)
    arrays = [jnp.asarray(tabs[i]) for i in table_idxs]
    arrays += [jnp.asarray(cols[n]) for n in names]

    from ..util import costmodel
    from ..util.kerneltel import TEL

    call_args = (
        jnp.asarray(ints), jnp.asarray(floats),
        jnp.asarray(n_spans, np.int32), jnp.asarray(t0_rel, np.int32),
        jnp.asarray(np.int32(max(1, step_ms))),
        jnp.asarray(np.int32(n_buckets)),
        jnp.asarray(np.asarray(gid, np.int32)),
        jnp.asarray(np.asarray(val, np.float32)),
        jnp.asarray(np.asarray(pres, bool)), *arrays)
    with TEL.launch(
        "mesh_timeseries",
        ("ts", tree, conds, names, has_val, G_b, NB_b, NT, B, S, table_idxs), S,
        cost=lambda: costmodel.spec(fn, *call_args, mesh=m1)):
        from .mesh import DISPATCH_LOCK

        with DISPATCH_LOCK:  # collective programs must not interleave enqueues
            outs = fn(*call_args)
            res = tuple(np.asarray(o)[:n_groups, :n_buckets] for o in outs)
    return res
