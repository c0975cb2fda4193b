"""Tier B of the cache plane: a host-RAM compressed column-chunk pool
under the HBM staged cache (ops/stage).

When the staged-column LRU evicts a column to stay under the HBM
budget, the padded device array is pulled back to host and parked
here instead of discarded -- the bytes already paid object-store IO,
decompression AND pad/assemble once. Entries are stored raw by
default and optionally recompressed through the block codec layer
(block/blockcodecs): a restage must beat the backend read + decode +
assemble it replaces, and without a native codec wheel the
compression round trip costs more than the RAM it saves. An entry is
ONE column, keyed (block, ((device column name,), group range)): a
later stage that misses that column in HBM decompresses and re-uploads
it straight from the pool: no backend ranged read, no column decode, no
owner-offset assembly. The pool is per-process, which under PR-7
affinity placement means per cache domain -- the queries that staged an
entry are the ones routed back to the process holding its demotion.

Demotion happens OUTSIDE the stage LRU lock (stage.py collects victims
under the lock and drains them after release): device->host transfers
and compression are milliseconds, the lock protects microsecond
bookkeeping.

Knobs (config_registry): TEMPO_CHUNK_CACHE (kill switch; 0 restores
discard-on-evict exactly), TEMPO_CHUNK_CACHE_BUDGET (compressed-byte
pool bound), TEMPO_CHUNK_CACHE_MAX_ENTRY (per-entry raw-byte admission
cap), TEMPO_CHUNK_CACHE_MIN_REUSE (stagings of a key before its
demotion is worth host RAM), TEMPO_CHUNK_CACHE_CODEC
(lz4/snappy/zstd/none).
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from .. import config_registry as _cfg
from ..util.profiler import timed_lock


def enabled() -> bool:
    return _cfg.get_bool("TEMPO_CHUNK_CACHE")


def _budget() -> int:
    return max(0, _cfg.get_int("TEMPO_CHUNK_CACHE_BUDGET"))


def _max_entry() -> int:
    return max(0, _cfg.get_int("TEMPO_CHUNK_CACHE_MAX_ENTRY"))


def _min_reuse() -> int:
    return max(1, _cfg.get_int("TEMPO_CHUNK_CACHE_MIN_REUSE"))


# ---------------------------------------------------------------- codecs
def _codec_pair(name: str):
    """(compress(bytes) -> bytes, decompress(bytes, raw_len) -> bytes).
    raw_len travels out of band in the entry, matching the colio
    convention."""
    if name == "none":
        return (lambda d: d), (lambda d, n: d)
    if name == "zstd":
        from ..util import zstdshim

        return (lambda d: zstdshim.ZstdCompressor(3).compress(d),
                lambda d, n: zstdshim.ZstdDecompressor().decompress(
                    d, max_output_size=n))
    from ..block import blockcodecs

    if name == "snappy":
        return blockcodecs.snappy_compress, blockcodecs.snappy_decompress
    # default: lz4 -- the cheapest round trip in the codec layer
    return blockcodecs.lz4_compress, blockcodecs.lz4_decompress


def codec_name() -> str:
    name = (_cfg.get("TEMPO_CHUNK_CACHE_CODEC") or "none").lower()
    return name if name in ("lz4", "snappy", "zstd", "none") else "none"


@dataclass
class _Entry:
    """One demoted staged column: the padded array's compressed bytes
    plus what restage() needs to rebuild it bit-identically. The
    request's shape fields are not here: ops/stage derives them from
    the block's footer and the group range."""

    dtype: str
    shape: tuple
    blob: bytes
    codec: str
    raw_bytes: int
    comp_bytes: int


# a cataloged hot lock, like stage_lru (TEMPO_LOCK_PROFILE arms timing)
_pool_lock = timed_lock("chunk_pool")
_pool: OrderedDict[tuple[str, tuple], _Entry] = OrderedDict()
_pool_bytes = 0
# (block_id, key) -> times stage_block built/looked for this entry; the
# bytesxreuse admission signal (entries staged once and never again are
# not worth host RAM when MIN_REUSE > 1)
_stage_counts: dict[tuple[str, tuple], int] = {}
_STAGE_COUNTS_MAX = 4096


def _tel():
    from ..util.kerneltel import TEL

    return TEL


def note_stage(block_id: str, key: tuple) -> None:
    """Record one staging of (block, key) -- the reuse signal demote
    admission checks."""
    if not enabled():
        return
    with _pool_lock:
        if len(_stage_counts) >= _STAGE_COUNTS_MAX and (
                block_id, key) not in _stage_counts:
            _stage_counts.clear()  # coarse reset; admission degrades soft
        _stage_counts[(block_id, key)] = _stage_counts.get(
            (block_id, key), 0) + 1


def _evict_over_budget_locked() -> None:
    global _pool_bytes
    budget = _budget()
    while _pool_bytes > budget and _pool:
        _, ent = _pool.popitem(last=False)
        _pool_bytes -= ent.comp_bytes
        _tel().chunk_cache_evictions.inc()
    _tel().chunk_cache_bytes.set(_pool_bytes)


def demote(block_id: str, key: tuple, arr) -> bool:
    """Compress one evicted staged column (a padded device array) into
    the pool under `key` = ((device column name,), groups). Called by
    ops/stage AFTER releasing the stage LRU lock. Returns whether the
    entry was admitted."""
    global _pool_bytes
    if not enabled() or not block_id or arr is None:
        return False
    pk = (block_id, key)
    with _pool_lock:
        if pk in _pool:  # already demoted once; just re-rank it
            _pool.move_to_end(pk)
            return True
        reuse = _stage_counts.get(pk, 1)
    if int(arr.nbytes) > _max_entry() or reuse < _min_reuse():
        return False
    name = codec_name()
    comp_fn, _ = _codec_pair(name)
    # device -> host pull; contiguous bytes for the codec
    host = np.ascontiguousarray(np.asarray(arr))
    blob = comp_fn(host.tobytes())
    ent = _Entry(dtype=str(host.dtype), shape=host.shape, blob=blob,
                 codec=name, raw_bytes=host.nbytes, comp_bytes=len(blob))
    with _pool_lock:
        if pk in _pool:
            _pool.move_to_end(pk)
            return True
        _pool[pk] = ent
        _pool_bytes += ent.comp_bytes
        _tel().chunk_cache_demotions.inc()
        _evict_over_budget_locked()
    return True


def probe(block_id: str, key: tuple) -> bool:
    """Whether a restage of (block, key) would hit -- the plan-time
    check stream pipelines use to skip issuing backend ranged reads."""
    if not enabled():
        return False
    with _pool_lock:
        return (block_id, key) in _pool


def restage(block_id: str, keys: list[tuple]) -> dict:
    """Rebuild the device columns of `keys` that the pool holds:
    decompress on host, ONE batched device upload. Returns key -> device
    array for the hits. Counts hits/misses per column and attaches a
    cache:chunk-hit span to the active self-trace."""
    if not enabled() or not keys:
        return {}
    tel = _tel()
    found: dict[tuple, _Entry] = {}
    with _pool_lock:
        for key in keys:
            ent = _pool.get((block_id, key))
            if ent is not None:
                _pool.move_to_end((block_id, key))
                found[key] = ent
    tel.chunk_cache_misses.inc(len(keys) - len(found))
    if not found:
        return {}
    import jax

    raw = sum(e.raw_bytes for e in found.values())
    with tel.stage("cache:chunk-hit", block=block_id[:8], bytes=raw,
                   columns=len(found)):
        host = [
            np.frombuffer(_codec_pair(e.codec)[1](e.blob, e.raw_bytes),
                          dtype=e.dtype).reshape(e.shape)
            for e in found.values()
        ]
        # ONE batched transfer, same as upload_stage: per-array device_puts
        # each pay a full link round trip
        devs = jax.device_put(host)
    # a host->device upload like any other staging miss's (the rows
    # were counted, real and padded, when the column was first staged)
    tel.record_transfer(raw, 0, 0)
    tel.chunk_cache_hits.inc(len(found))
    return dict(zip(found, devs))


def stats() -> dict:
    """Point-in-time pool view for /status/kernels."""
    tel = _tel()
    with _pool_lock:
        entries = len(_pool)
        comp = _pool_bytes
        raw = sum(e.raw_bytes for e in _pool.values())
    return {
        "enabled": enabled(),
        "codec": codec_name(),
        "entries": entries,
        "compressed_bytes": int(comp),
        "raw_bytes": int(raw),
        "budget_bytes": _budget(),
        "hits": int(tel.chunk_cache_hits.get()),
        "misses": int(tel.chunk_cache_misses.get()),
        "demotions": int(tel.chunk_cache_demotions.get()),
        "evictions": int(tel.chunk_cache_evictions.get()),
    }


def clear() -> None:
    """Drop everything (tests + budget reconfiguration)."""
    global _pool_bytes
    with _pool_lock:
        _pool.clear()
        _stage_counts.clear()
        _pool_bytes = 0
        _tel().chunk_cache_bytes.set(0)

