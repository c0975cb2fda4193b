"""Block-cut kernels: the flush path's device-side heavy lifting.

When the ingester cuts a head block, three per-row host loops dominate
the wall time (ISSUE 16): dictionary finalization remaps every code
column through the sorted-order permutation, the trace-id bloom sets
K=7 bits per trace, and row-group pruning stats take a min/max per
column slice. Each is a gather / scatter-OR / segmented-reduce -- VPU
shapes -- so they run here as jitted kernels with bit-identical numpy
twins (pure integer ops, so device == host EXACTLY, registered in
ops/twins.py). The builder routes through cut_engine() and falls back
to its original host code when jax or a device backend is absent.

Bucketed shapes keep compiled-program count logarithmic (ops/device):
pad codes with -1 (remap passes negatives through unchanged), pad bloom
scatter entries with (word 0, bits 0) no-ops, pad row-group ids into a
trash segment that is sliced away.
"""

from __future__ import annotations

import os
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..block.bloom import _K, WORD_BITS, shard_for_trace_id
from ..util.hashing import bloom_hashes
from .device import bucket, pad_rows, scoped

_I32_MIN = -(2**31)
_I32_MAX = 2**31 - 1


def cut_engine() -> str:
    """'device' | 'host' for this process's block cuts. TEMPO_CUT_ENGINE
    overrides; otherwise device kernels engage only on a real
    accelerator backend (on cpu-jax the jit round trip loses to numpy)."""
    from ..util.kerneltel import TEL

    eng = os.environ.get("TEMPO_CUT_ENGINE", "").strip().lower()
    if eng in ("device", "host"):
        reason = "env"
    else:
        eng = "device" if jax.default_backend() != "cpu" else "host"
        reason = "backend"
    TEL.record_routing("block_cut", eng, reason)
    return eng


# ---------------------------------------------------------------- remap
@lru_cache(maxsize=None)
def _compiled_remap(n_b: int, r_b: int):
    def kern(col, remap):
        return jnp.where(col >= 0, remap[jnp.maximum(col, 0)], col)

    return jax.jit(scoped("cut_remap")(kern))


def remap_codes_device(col: np.ndarray, remap: np.ndarray) -> np.ndarray:
    """Dictionary-finalize remap of one code column: negatives (absent /
    sentinel codes) pass through, everything else gathers through the
    sort permutation. Twin: remap_codes_host."""

    from ..util.kerneltel import TEL

    n, r = len(col), len(remap)
    n_b, r_b = bucket(n), bucket(r)
    col_p = pad_rows(np.asarray(col, dtype=np.int32), n_b, -1)
    rm_p = pad_rows(np.asarray(remap, dtype=np.int32), r_b, 0)
    fn = _compiled_remap(n_b, r_b)
    with TEL.launch("cut_remap", ("remap", n_b, r_b), n_b):
        out = np.asarray(fn(jnp.asarray(col_p), jnp.asarray(rm_p)))[:n]
    return out.astype(np.int32)


def remap_codes_host(col: np.ndarray, remap: np.ndarray) -> np.ndarray:
    """Pure-numpy twin of remap_codes_device (== dictionary.apply_remap)."""
    col = np.asarray(col, dtype=np.int32)
    remap = np.asarray(remap, dtype=np.int32)
    return np.where(col >= 0, remap[np.maximum(col, 0)], col).astype(np.int32)


# ---------------------------------------------------------------- bloom
def _bloom_scatter(trace_ids: list[bytes], n_shards: int, shard_bits: int):
    """Host control plane: hash every id to (global word index, bit
    word) scatter pairs, DEDUPED so a scatter-add of single-bit words
    equals the scatter-OR the filter semantics need."""
    n_words_per_shard = shard_bits // WORD_BITS
    keys = set()
    for tid in trace_ids:
        base = shard_for_trace_id(tid, n_shards) * shard_bits
        for pos in bloom_hashes(tid, _K, shard_bits):
            keys.add(base + pos)  # global bit index
    bit_idx = np.fromiter(keys, dtype=np.int64, count=len(keys))
    word_idx = (bit_idx // WORD_BITS).astype(np.int32)
    bits = (np.uint32(1) << (bit_idx % WORD_BITS).astype(np.uint32)).astype(np.uint32)
    return word_idx, bits, n_shards * n_words_per_shard


@lru_cache(maxsize=None)
def _compiled_bloom(n_b: int, n_words: int):
    def kern(flat, word_idx, bits):
        # entries are distinct bits, so the scatter-ADD of one-hot words
        # is exactly the scatter-OR; pads add 0 to word 0 (a no-op)
        return flat | jnp.zeros(n_words, jnp.uint32).at[word_idx].add(bits)

    return jax.jit(scoped("cut_bloom")(kern))


def bloom_bits_device(words: np.ndarray, trace_ids: list[bytes],
                      shard_bits: int) -> np.ndarray:
    """Set every trace id's K bloom bits in a (n_shards, W) word array,
    returning the updated array. Twin: bloom_bits_host."""

    from ..util.kerneltel import TEL

    n_shards = words.shape[0]
    word_idx, bits, n_words = _bloom_scatter(trace_ids, n_shards, shard_bits)
    n_b = bucket(len(word_idx))
    word_idx = pad_rows(word_idx, n_b, 0)
    bits = pad_rows(bits, n_b, 0)
    fn = _compiled_bloom(n_b, n_words)
    with TEL.launch("cut_bloom", ("bloom", n_b, n_words), n_b):
        out = np.asarray(fn(jnp.asarray(words.reshape(-1)), jnp.asarray(word_idx),
                            jnp.asarray(bits)))
    return out.reshape(words.shape)


def bloom_bits_host(words: np.ndarray, trace_ids: list[bytes],
                    shard_bits: int) -> np.ndarray:
    """Pure-numpy twin of bloom_bits_device (== ShardedBloom.add loop)."""
    out = words.copy()
    n_shards = out.shape[0]
    for tid in trace_ids:
        shard = shard_for_trace_id(tid, n_shards)
        for pos in bloom_hashes(tid, _K, shard_bits):
            out[shard, pos // WORD_BITS] |= np.uint32(1 << (pos % WORD_BITS))
    return out


# ----------------------------------------------------------- row groups
@lru_cache(maxsize=None)
def _compiled_rowgroup(n_b: int, n_seg: int):
    def kern(gid, start_ms, dur_us):
        lo = jax.ops.segment_min(start_ms, gid, num_segments=n_seg)
        hi = jax.ops.segment_max(start_ms, gid, num_segments=n_seg)
        du = jax.ops.segment_max(dur_us, gid, num_segments=n_seg)
        return lo, hi, du

    return jax.jit(scoped("cut_rowgroups")(kern))


def rowgroup_minmax_device(start_ms: np.ndarray, dur_us: np.ndarray,
                           bounds: list[int]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-row-group (start_ms min, start_ms max, dur_us max) pruning
    stats as one segmented reduce. bounds are the group boundaries
    (len n_groups+1, covering every row, all groups non-empty).
    Twin: rowgroup_minmax_host."""

    from ..util.kerneltel import TEL

    n_groups = len(bounds) - 1
    n = int(bounds[-1])
    gid = np.repeat(np.arange(n_groups, dtype=np.int32), np.diff(bounds))
    n_b = bucket(n)
    gid = pad_rows(gid, n_b, n_groups)  # pads land in a trash segment
    sm = pad_rows(np.asarray(start_ms, dtype=np.int32), n_b, 0)
    du = pad_rows(np.asarray(dur_us, dtype=np.int32), n_b, 0)
    fn = _compiled_rowgroup(n_b, n_groups + 1)
    with TEL.launch("cut_rowgroups", ("rowgroups", n_b, n_groups + 1), n_b):
        lo, hi, dmax = fn(jnp.asarray(gid), jnp.asarray(sm), jnp.asarray(du))
        out = (np.asarray(lo)[:n_groups], np.asarray(hi)[:n_groups],
               np.asarray(dmax)[:n_groups])
    return out


def rowgroup_minmax_host(start_ms: np.ndarray, dur_us: np.ndarray,
                         bounds: list[int]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pure-numpy twin of rowgroup_minmax_device (per-slice reductions,
    == the builder's original per-group loop)."""
    n_groups = len(bounds) - 1
    lo = np.empty(n_groups, dtype=np.int32)
    hi = np.empty(n_groups, dtype=np.int32)
    du = np.empty(n_groups, dtype=np.int32)
    for g in range(n_groups):
        a, b = bounds[g], bounds[g + 1]
        lo[g] = start_ms[a:b].min()
        hi[g] = start_ms[a:b].max()
        du[g] = dur_us[a:b].max()
    return lo, hi, du
