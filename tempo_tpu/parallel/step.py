"""The composed multi-chip step: find + search + bloom union in ONE jit.

This is the whole read+compact hot path as a single mesh program --
what the driver's dryrun compiles, and the shape production queries run
as: sharded trace-ID lookup (dp x sp, pmax combine), sharded predicate
search (dp blocks, sp rows, psum combine), and the compaction bloom
union (all_gather + OR). One compile, three collectives, zero host
round-trips between stages.
"""

from __future__ import annotations

from functools import lru_cache

import jax

from ..ops.device import scoped
from ..ops.filter import normalize_tree
from .bloom import make_sharded_union
from .find import make_sharded_find
from .search import make_sharded_search


@lru_cache(maxsize=32)
def distributed_query_step(mesh, tree, conds, col_names: tuple[str, ...],
                           B: int, T: int, Q: int, S: int, R: int, NT: int,
                           K: int, NS: int, W: int):
    """Returns jit(fn)(ids, n_valid, queries, ops_i, ops_f, n_spans,
    col_arrays, blooms) -> (hits (Q,2) [block,row], trace_mask (B,NT),
    span_count (B,NT), bloom_union (NS,W))."""
    conds = tuple(conds)
    if tree is not None:
        tree = normalize_tree(tree, conds)
    find_fn = make_sharded_find(mesh, B, T, Q)
    search_fn = make_sharded_search(mesh, tree, conds, col_names, B, S, R, NT)
    union_fn = make_sharded_union(mesh, K, NS, W)

    def step(ids, n_valid, queries, ops_i, ops_f, n_spans, col_arrays, blooms):
        import jax.numpy as jnp

        hits = find_fn(ids, n_valid, queries)
        # search operands are per-block (B, C, ...); the composed step takes
        # one operand set and replicates it across blocks
        ops_bi = jnp.broadcast_to(ops_i[None], (B,) + ops_i.shape)
        ops_bf = jnp.broadcast_to(ops_f[None], (B,) + ops_f.shape)
        tm, sc = search_fn(ops_bi, ops_bf, n_spans, *col_arrays)
        bu = union_fn(blooms)
        return hits, tm, sc, bu

    fn = jax.jit(scoped("mesh_step")(step))

    def launcher(ids, n_valid, queries, ops_i, ops_f, n_spans, col_arrays, blooms):
        """Thin telemetry shim over the jitted step: the driver calls
        this like the jit fn; the first call per shape also captures the
        composed program's XLA costs + collective comm bytes
        (util/costmodel -- find's pmax, search's psum, union's
        all_gather all in ONE walk)."""
        from ..util import costmodel
        from ..util.kerneltel import TEL

        args = (ids, n_valid, queries, ops_i, ops_f, n_spans, col_arrays, blooms)
        with TEL.launch(
            "mesh_step", ("step", B, T, Q, S, R, NT, K, NS, W), S,
            cost=lambda: costmodel.spec(fn, *args, mesh=mesh)):
            out = fn(*args)
        return out

    return launcher
