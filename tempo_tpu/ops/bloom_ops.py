"""Bloom filter kernels: batch membership test and compaction union.

The union is the north-star "pmap'd sketch union" (BASELINE.json): when
compaction inputs share bloom geometry, the output block's filter is a
single elementwise OR over stacked (n_blocks, n_shards, words) bits --
one fused VPU pass instead of the reference's per-key re-insertion
(v2/streaming_block.go bloom adds during merge).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..block.bloom import ShardedBloom, shard_for_trace_id
from ..util.hashing import bloom_hashes
from .device import scoped


@jax.jit
@scoped("bloom_union")
def _union_kernel(stacked: jnp.ndarray) -> jnp.ndarray:
    """(K, n_shards, words) uint32 -> (n_shards, words) bitwise-OR union."""
    return jax.lax.reduce(
        stacked, jnp.uint32(0), jax.lax.bitwise_or, dimensions=(0,)
    )


def union_blooms(blooms: list[ShardedBloom]) -> ShardedBloom:
    """Device union of same-geometry blooms; falls back to ValueError on
    geometry mismatch (caller rebuilds instead)."""

    from ..util.kerneltel import TEL

    first = blooms[0]
    for b in blooms[1:]:
        if b.n_shards != first.n_shards or b.shard_bits != first.shard_bits:
            raise ValueError("bloom geometry mismatch")
    stacked = jnp.asarray(np.stack([b.words for b in blooms]))
    with TEL.launch("bloom_union", ("union", stacked.shape), stacked.shape[0]):
        out = ShardedBloom(first.n_shards, first.shard_bits)
        out.words = np.asarray(_union_kernel(stacked))
    return out


@jax.jit
@scoped("bloom_test")
def _test_kernel(words: jnp.ndarray, word_idx: jnp.ndarray, bit_idx: jnp.ndarray) -> jnp.ndarray:
    """words: (S, W) u32; word_idx/bit_idx: (Q, K) per-query bloom positions
    (word_idx pre-offset by query shard * W is NOT needed -- words indexed
    per query via first column of word_idx... see batch_test)."""
    gathered = words[word_idx[..., 0], word_idx[..., 1]]  # (Q, K)
    bits = (gathered >> bit_idx.astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.all(bits == 1, axis=-1)


def batch_test(bloom_words: np.ndarray, shard_bits: int, n_shards: int, trace_ids: list[bytes]) -> np.ndarray:
    """Test many trace ids against a block's full bloom (n_shards, W).
    Hash positions are host-computed (cheap, control plane); the bit
    gather+AND runs on device."""
    q = len(trace_ids)
    if q == 0:
        return np.zeros(0, dtype=bool)
    k = len(bloom_hashes(b"x", 7, shard_bits))
    word_idx = np.zeros((q, k, 2), dtype=np.int32)
    bit_idx = np.zeros((q, k), dtype=np.int32)
    for i, tid in enumerate(trace_ids):
        shard = shard_for_trace_id(tid, n_shards)
        for j, pos in enumerate(bloom_hashes(tid, 7, shard_bits)):
            word_idx[i, j] = (shard, pos // 32)
            bit_idx[i, j] = pos % 32

    from ..util.kerneltel import TEL

    with TEL.launch("bloom_test", ("test", bloom_words.shape, q, k),
                      bloom_words.shape[1]):
        out = np.asarray(
            _test_kernel(jnp.asarray(bloom_words), jnp.asarray(word_idx), jnp.asarray(bit_idx))
        )
    return out
