"""Distributed bloom/sketch union: the compaction collective.

The north-star "pmap'd sketch union" (BASELINE.json): compacting K
blocks unions K same-geometry sharded blooms. Input filters shard over
the mesh, each chip ORs its slice locally, and an `all_gather` + OR
produces the replicated result -- one pass over ICI instead of the
reference's per-key re-insertion during merge (v2/streaming_block.go).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..block.bloom import ShardedBloom
from ..ops.device import scoped
from .mesh import smap


@lru_cache(maxsize=64)
def make_sharded_union(mesh, K: int, NS: int, W: int):
    """(K, NS, W) uint32 stacked blooms, K sharded over the whole mesh ->
    (NS, W) replicated union."""

    def local(stacked_l):
        acc = jax.lax.reduce(stacked_l, jnp.uint32(0), jax.lax.bitwise_or, dimensions=(0,))
        gathered = jax.lax.all_gather(acc, "sp")
        acc = jax.lax.reduce(gathered, jnp.uint32(0), jax.lax.bitwise_or, dimensions=(0,))
        gathered = jax.lax.all_gather(acc, "dp")
        return jax.lax.reduce(gathered, jnp.uint32(0), jax.lax.bitwise_or, dimensions=(0,))

    fn = smap(scoped("mesh_bloom")(local), mesh, in_specs=(P(("dp", "sp")),),
              out_specs=P())
    return jax.jit(fn)


def sharded_bloom_union(mesh, blooms: list[ShardedBloom]) -> ShardedBloom:
    """Union many same-geometry blooms across the mesh."""
    first = blooms[0]
    for b in blooms[1:]:
        if b.n_shards != first.n_shards or b.shard_bits != first.shard_bits:
            raise ValueError("bloom geometry mismatch")
    n = mesh.devices.size
    K = ((len(blooms) + n - 1) // n) * n
    stacked = np.zeros((K,) + first.words.shape, dtype=np.uint32)
    for i, b in enumerate(blooms):
        stacked[i] = b.words
    fn = make_sharded_union(mesh, K, first.words.shape[0], first.words.shape[1])

    from ..util import costmodel
    from ..util.kerneltel import TEL

    stacked_j = jnp.asarray(stacked)
    with TEL.launch("mesh_bloom", ("union", K, first.words.shape), K,
                      cost=lambda: costmodel.spec(fn, stacked_j, mesh=mesh)):
        out = ShardedBloom(first.n_shards, first.shard_bits)
        from .mesh import DISPATCH_LOCK

        with DISPATCH_LOCK:  # collective programs must not interleave enqueues
            out.words = np.asarray(fn(stacked_j))
    return out
