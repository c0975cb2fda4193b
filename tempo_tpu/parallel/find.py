"""Sharded trace-ID lookup: the multi-chip Find.

The reference fans trace-by-ID out per candidate block over a goroutine
pool (tempodb/tempodb.go:271-352 Find + tempodb/pool) and across
queriers via trace-ID-space shards (modules/frontend/
tracebyidsharding.go). Here every chip holds a slice of the stacked
per-block sorted trace-id indexes, runs the same batched bisection
locally (ops/find.py), and a single `pmax` over the mesh merges hits --
the combiner is an ICI collective instead of a host merge loop.

A hit is the (global_block, row) pair, combined in two pmax stages:
first the mesh elects the max hit-holding block id per query, then the
winner's shard contributes the row. max() is a valid combiner because
each trace id lives in >= 1 block row and any duplicate (compaction
overlap) resolves deterministically to the highest block -- callers
treat hits as candidates to materialize + combine, same as the
reference's partial-trace combiner.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import smap
from ..ops.device import bucket, pad_rows, scoped
from ..ops.find import bisect_ids


@lru_cache(maxsize=64)
def make_sharded_find(mesh, B: int, T: int, Q: int):
    """Build the jitted mesh program for fixed (padded) shapes.

    ids: (B, T, 4) int32, blocks sharded over the flattened (dp, sp) axis;
    n_valid: (B,); queries: (Q, 4) replicated.
    Returns (Q, 2) int32 [global_block, row], (-1, -1) on miss.
    """
    n_steps = int(T).bit_length()

    def local(ids_l, n_valid_l, queries):
        # ids_l: (B/n, T, 4) — this shard's blocks
        Bl = ids_l.shape[0]
        sids = jax.vmap(lambda a, nv: bisect_ids(a, queries, nv, n_steps))(
            ids_l, n_valid_l
        )  # (Bl, Q)
        # psum(1, axis) == axis size (jax.lax.axis_size is not in this
        # jax release)
        sp_size = jax.lax.psum(1, "sp")
        shard = jax.lax.axis_index("dp") * sp_size + jax.lax.axis_index("sp")
        gblock = shard * Bl + jnp.arange(Bl, dtype=jnp.int32)[:, None]  # (Bl, 1)
        # two-stage combine, no block*T+row packing (would overflow i32):
        # 1) pmax elects the winning block id per query
        blk = jnp.where(sids >= 0, gblock, -1)  # (Bl, Q)
        best_blk = jnp.max(blk, axis=0)
        best_blk = jax.lax.pmax(jax.lax.pmax(best_blk, "sp"), "dp")  # (Q,)
        # 2) only the winner's shard contributes its row, pmax broadcasts it
        row = jnp.where(blk == best_blk[None, :], sids, -1)
        row = jnp.max(row, axis=0)
        row = jax.lax.pmax(jax.lax.pmax(row, "sp"), "dp")
        return jnp.stack([best_blk, row], axis=-1)  # (Q, 2)

    fn = smap(scoped("mesh_find")(local), mesh,
        in_specs=(P(("dp", "sp")), P(("dp", "sp")), P()),
        out_specs=P(),
    )
    return jax.jit(fn)


@lru_cache(maxsize=64)
def make_sharded_find_rows(mesh, B: int, T: int, Q: int):
    """Like make_sharded_find but each block reports its OWN hit row:
    returns (B, Q) int32 sids (-1 miss), block axis sharded over the
    flattened mesh. This is the service-path Find: every block holding
    the id contributes a partial trace for the host combiner
    (wire/combine.py), matching the reference's Find + combiner
    (tempodb/tempodb.go:271-352) instead of electing one winner."""
    n_steps = int(T).bit_length()

    def local(ids_l, n_valid_l, queries):
        return jax.vmap(lambda a, nv: bisect_ids(a, queries, nv, n_steps))(ids_l, n_valid_l)

    fn = smap(scoped("mesh_find")(local), mesh,
        in_specs=(P(("dp", "sp")), P(("dp", "sp")), P()),
        out_specs=P(("dp", "sp")),
    )
    return jax.jit(fn)


def sharded_find_rows(mesh, id_code_arrays: list[np.ndarray], query_codes: np.ndarray) -> np.ndarray:
    """Host entry for the per-block-rows Find. Returns (B, Q) int32
    row-in-block (-1 miss), B = len(id_code_arrays)."""
    n = mesh.devices.size
    q = query_codes.shape[0]
    if not id_code_arrays or q == 0:
        return np.full((len(id_code_arrays), q), -1, dtype=np.int32)
    ids, n_valid, T = stack_block_ids(id_code_arrays, n)
    Qb = bucket(q)
    queries = pad_rows(np.asarray(query_codes, np.int32), Qb, np.int32(-(2**31)))
    fn = make_sharded_find_rows(mesh, ids.shape[0], T, Qb)

    from ..util import costmodel
    from ..util.kerneltel import TEL

    ids_j, nv_j, q_j = jnp.asarray(ids), jnp.asarray(n_valid), jnp.asarray(queries)
    with TEL.launch(
        "mesh_find", ("rows", ids.shape[0], T, Qb), T,
        cost=lambda: costmodel.spec(fn, ids_j, nv_j, q_j, mesh=mesh)):
        from .mesh import DISPATCH_LOCK

        with DISPATCH_LOCK:  # collective programs must not interleave enqueues
            out = np.asarray(fn(ids_j, nv_j, q_j))
    return out[: len(id_code_arrays), :q]


def stack_block_ids(id_code_arrays: list[np.ndarray], n_shards: int) -> tuple[np.ndarray, np.ndarray, int]:
    """Stack per-block sorted id-code arrays (Ti, 4) into (B, T, 4) padded
    for an n_shards-way mesh: T = common power-of-two bucket, B padded to a
    multiple of n_shards with empty blocks. Returns (ids, n_valid, T)."""
    B = len(id_code_arrays)
    T = bucket(max([a.shape[0] for a in id_code_arrays] + [1]))
    Bp = ((B + n_shards - 1) // n_shards) * n_shards if B else n_shards
    ids = np.full((Bp, T, 4), np.int32(2**31 - 1), dtype=np.int32)
    n_valid = np.zeros((Bp,), dtype=np.int32)
    for i, a in enumerate(id_code_arrays):
        ids[i, : a.shape[0]] = a
        n_valid[i] = a.shape[0]
    return ids, n_valid, T


def sharded_find(mesh, id_code_arrays: list[np.ndarray], query_codes: np.ndarray) -> np.ndarray:
    """Host entry: look up Q trace ids across many blocks on the mesh.
    Returns (Q, 2) int32 [block, row] (-1,-1 on miss)."""
    n = mesh.devices.size
    q = query_codes.shape[0]
    if not id_code_arrays or q == 0:
        return np.full((q, 2), -1, dtype=np.int32)
    ids, n_valid, T = stack_block_ids(id_code_arrays, n, )
    Qb = bucket(q)
    queries = pad_rows(np.asarray(query_codes, np.int32), Qb, np.int32(-(2**31)))
    fn = make_sharded_find(mesh, ids.shape[0], T, Qb)

    from ..util import costmodel
    from ..util.kerneltel import TEL

    ids_j, nv_j, q_j = jnp.asarray(ids), jnp.asarray(n_valid), jnp.asarray(queries)
    with TEL.launch(
        "mesh_find", ("elect", ids.shape[0], T, Qb), T,
        cost=lambda: costmodel.spec(fn, ids_j, nv_j, q_j, mesh=mesh)):
        from .mesh import DISPATCH_LOCK

        with DISPATCH_LOCK:  # collective programs must not interleave enqueues
            out = np.asarray(fn(ids_j, nv_j, q_j))[:q]
    out = out.astype(np.int32, copy=True)
    out[out[:, 0] < 0] = -1  # normalize misses to (-1, -1)
    return out
