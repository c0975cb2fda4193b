"""Mesh-batched multi-query search: one admission window -> ALL chips.

ops/multiquery.py fuses a PR-3 admission window's Q queries into one
single-chip launch; this module is the same fixed-shape predicate
program as a shard_map so the window's ONE launch also spans every
device: the staged span axis shards over the whole mesh (both axes
flattened -- a single block has no 'dp' fan-out to ride), each chip
interprets all Q packed programs against its row slice, and one psum
stitches the per-trace counts. Concurrency (the Q axis) and
chip-parallelism (the row axis) therefore multiply instead of
competing for the executor -- the ROADMAP 2c "fuse it with batching"
leg.

Bit-identity: every per-shard fold is the same cumsum + offset-gather
segment fold as the single-chip interpreter, shifted by the shard's
global row base and clipped to its slice; the psum adds exact int32
partials, so (trace_mask, counts) equal ops/multiquery.eval_multiquery
bit for bit (tests/test_mesh_batch.py holds the differential).

Launch keys are shape-only -- (ProgramShape, Q-bucket, axis buckets,
mesh) -- exactly the coalesce-key discipline of the single-chip path:
operand tables stay traced, so windows with different constants share
one compiled mesh program.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.device import PAD_I32, scoped
from ..ops.multiquery import ProgramShape, _cmp_code
from .mesh import smap


@lru_cache(maxsize=32)
def make_mesh_multiquery(mesh, shape: ProgramShape, q_b: int,
                         n_spans_b: int, n_traces_b: int):
    """Jitted Q-programs x sharded-rows program over `mesh`.

    Inputs: span_mat (n_sc, S) int32 row-sharded over every mesh axis;
    trace_mat (n_tc, NT), span_off (NT+1,), the packed program tables
    (ops/multiquery.pack_queries) and the real row counts, all
    replicated. Returns replicated (q_b, NT) (trace_mask, counts)."""
    n_sc = max(1, len(shape.span_cols))
    n_tc = max(1, len(shape.trace_cols))
    axes = tuple(mesh.axis_names)  # row axis shards over ALL mesh axes

    def local(span_mat, trace_mat, span_off, progs, n_spans, n_traces):
        Sl = span_mat.shape[1]
        shard = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        row0 = shard * Sl
        valid_span = (jnp.arange(Sl, dtype=jnp.int32) + row0) < n_spans
        valid_trace = jnp.arange(n_traces_b, dtype=jnp.int32) < n_traces
        lo = jnp.clip(span_off[:-1] - row0, 0, Sl)
        hi = jnp.clip(span_off[1:] - row0, 0, Sl)

        def seg_partial(masks):
            """(G, Sl) row masks -> (G, NT) PARTIAL per-trace counts:
            local cumsum + global-offset gathers clipped to this
            shard's slice (ops/filter._offset_counts shifted by row0);
            the caller psums the partials."""
            cs = jnp.concatenate(
                [jnp.zeros((masks.shape[0], 1), jnp.int32),
                 jnp.cumsum(masks.astype(jnp.int32), axis=1)], axis=1)
            return cs[:, hi] - cs[:, lo]

        def fold_rows(p):
            """One program's span-level half on the local row slice:
            per-group and union-mask partial per-trace counts."""
            x = span_mat[jnp.clip(p["cond_col"], 0, n_sc - 1)]
            m = _cmp_code(p["cond_op"][:, None], x,
                          p["cond_v0"][:, None], p["cond_v1"][:, None])
            m = m & (~p["cond_guard"][:, None] | (x != PAD_I32))
            m = m & valid_span[None, :]
            cs = jnp.concatenate(
                [jnp.zeros((1, Sl), jnp.int32),
                 jnp.cumsum(m.astype(jnp.int32), axis=0)])
            co = p["clause_off"]
            clause_ok = (cs[co[1:]] - cs[co[:-1]]) > 0
            cs2 = jnp.concatenate(
                [jnp.zeros((1, Sl), jnp.int32),
                 jnp.cumsum(clause_ok.astype(jnp.int32), axis=0)])
            go = p["group_off"]
            n_cl = (go[1:] - go[:-1])[:, None]
            grp_ok = ((cs2[go[1:]] - cs2[go[:-1]]) == n_cl) & valid_span[None, :]
            live = (jnp.arange(grp_ok.shape[0]) < p["n_groups"])[:, None]
            union = jnp.where(p["n_groups"] > 0,
                              jnp.any(grp_ok & live, axis=0), valid_span)
            return seg_partial(jnp.concatenate([grp_ok, union[None]]))

        parts = jax.vmap(fold_rows)(progs)  # (Q, NG+1, NT) partials
        counts_all = jax.lax.psum(parts, axes)  # ONE collective per launch
        gcounts, ucounts = counts_all[:, :-1], counts_all[:, -1]

        def combine(p, gcounts_q, ucounts_q):
            """Trace-level half on the replicated psummed counts --
            identical arithmetic on every shard, so the output needs no
            further collective."""
            gmask = gcounts_q > 0
            tx = trace_mat[jnp.clip(p["tcond_col"], 0, n_tc - 1)]
            tcm = _cmp_code(p["tcond_op"][:, None], tx,
                            p["tcond_v0"][:, None], p["tcond_v1"][:, None])
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
            return tm, jnp.where(tm, ucounts_q, 0)

        return jax.vmap(combine)(progs, gcounts, ucounts)

    row_spec = P(None, axes)  # row axis over every device, dp-major
    in_specs = (row_spec, P(), P(), P(), P(), P())
    fn = smap(scoped("mesh_multiquery")(local), mesh, in_specs=in_specs,
              out_specs=(P(), P()))
    return jax.jit(fn)


def mesh_batch_eligible(mesh, staged) -> bool:
    """Shape guard for the mesh-batched route: every device needs a
    whole slice of the padded span axis. Power-of-two buckets (>= 1024,
    ops/device.bucket) over power-of-two meshes always pass; odd
    virtual-device counts fall back to the single-chip fused launch."""
    n_dev = int(mesh.devices.size)
    return n_dev > 1 and staged.n_spans_b % n_dev == 0


def mesh_eval_multiquery(mesh, lowered: list, staged, progs: dict):
    """Run Q packed programs against one staged block as ONE launch
    across every mesh device. Same contract as
    ops/multiquery.eval_multiquery but returns host numpy (q_b, NT)
    arrays: the demux path slices per-query rows and mixing the mesh
    program's replicated outputs with single-device staged arrays in a
    later jit would force a device-mismatch reshard anyway."""

    from ..util import costmodel
    from ..util.kerneltel import TEL
    from .mesh import DISPATCH_LOCK

    shape = lowered[0].shape
    q_b = int(progs["cond_op"].shape[0])
    fn = make_mesh_multiquery(mesh, shape, q_b, staged.n_spans_b,
                              staged.n_traces_b)
    span_mat = (jnp.stack([staged.cols[n] for n in shape.span_cols])
                if shape.span_cols
                else jnp.zeros((1, staged.n_spans_b), jnp.int32))
    trace_mat = (jnp.stack([staged.cols[n] for n in shape.trace_cols])
                 if shape.trace_cols
                 else jnp.zeros((1, staged.n_traces_b), jnp.int32))
    args = (span_mat, trace_mat, staged.cols["trace.span_off"], progs,
            np.int32(staged.n_spans), np.int32(staged.n_traces))
    with TEL.launch(
        "mesh_multiquery",
        ("mmq", shape, q_b, staged.n_spans_b, staged.n_traces_b,
         tuple(mesh.shape.items())),
        staged.n_spans_b,
        cost=lambda: costmodel.spec(fn, *args, mesh=mesh),
        occupancy=len(lowered), devices=int(mesh.devices.size)) as ln:
        with DISPATCH_LOCK:  # collective programs must not interleave enqueues
            tm, counts = fn(*args)
            out = np.asarray(tm), np.asarray(counts)
        comm = costmodel.COST.comm_for("mesh_multiquery", str(staged.n_spans_b))
        ln.attrs["comm_bytes"] = int(sum(comm.values()))
    TEL.record_mesh_batch(len(lowered))
    return out
