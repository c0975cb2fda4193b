"""ctypes bindings for the native runtime layer (native/vtpu_native.cc).

Loads native/libvtpu_native.so (built by `make -C native`; auto-built
once if the toolchain is present), exposing batch hashing, bloom
insertion, WAL frame scanning and threaded zstd codecs. Every entry
point has a pure-Python fallback so the framework runs without the
shared library -- `available()` reports which path is active.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_LIB = None
_TRIED = False

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_SO = os.path.join(_NATIVE_DIR, "libvtpu_native.so")


def _load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    if not os.path.exists(_SO):
        try:  # one build attempt; fallbacks cover failure
            subprocess.run(["make", "-C", _NATIVE_DIR], capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError):
            pass
    for attempt in (0, 1):
        if not os.path.exists(_SO):
            break
        try:
            _LIB = _bind(ctypes.CDLL(_SO))
            break
        except (OSError, AttributeError):
            # AttributeError = a stale prebuilt .so missing a newer
            # symbol: rebuild ONCE, else run on the pure-Python fallbacks
            _LIB = None
            if attempt:
                break
            try:
                subprocess.run(["make", "-C", _NATIVE_DIR, "-B"],
                               capture_output=True, timeout=120)
            except (OSError, subprocess.SubprocessError):
                break
    if _LIB is None:
        from ..util.log import get_logger

        get_logger("native").warning(
            "native library %s unavailable (build it with `make -C native`): "
            "codecs, hashing and WAL scans run on the pure-Python fallbacks",
            os.path.normpath(_SO))
    return _LIB


def _bind(lib):
    lib.vtpu_ring_tokens.argtypes = [
        ctypes.c_char_p, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
    ]
    lib.vtpu_bloom_add_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
    ]
    lib.vtpu_varint_frames.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
    ]
    lib.vtpu_varint_frames.restype = ctypes.c_int
    lib.vtpu_zstd_bound.argtypes = [ctypes.c_int64]
    lib.vtpu_zstd_bound.restype = ctypes.c_int64
    lib.vtpu_zstd_compress_batch.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 2 + [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
    ]
    lib.vtpu_zstd_compress_batch.restype = ctypes.c_int
    lib.vtpu_zstd_decompress_batch.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 2 + [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int,
    ]
    lib.vtpu_zstd_decompress_batch.restype = ctypes.c_int
    # snappy/lz4 block codecs: batch signatures mirror the zstd ones
    # (minus the level param -- neither format has levels)
    lib.vtpu_snappy_bound.argtypes = [ctypes.c_int64]
    lib.vtpu_snappy_bound.restype = ctypes.c_int64
    lib.vtpu_lz4_bound.argtypes = [ctypes.c_int64]
    lib.vtpu_lz4_bound.restype = ctypes.c_int64
    batch_args = [ctypes.c_void_p] * 6 + [ctypes.c_int, ctypes.c_int]
    for fn in (lib.vtpu_snappy_compress_batch, lib.vtpu_snappy_decompress_batch,
               lib.vtpu_lz4_compress_batch, lib.vtpu_lz4_decompress_batch):
        fn.argtypes = batch_args
        fn.restype = ctypes.c_int
    lib.vtpu_dict_union.argtypes = [
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.vtpu_dict_union.restype = ctypes.c_int64
    lib.vtpu_gather_runs.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
    ]
    lib.vtpu_gather_runs_addr.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int64,
    ]
    lib.vtpu_gather_runs_remap.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.vtpu_gather_runs_remap.restype = ctypes.c_int64
    lib.vtpu_mask_cmp_i32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.vtpu_mask_cmp_i64.argtypes = lib.vtpu_mask_cmp_i32.argtypes
    lib.vtpu_mask_lut_i32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.vtpu_seg_count_mask.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.vtpu_slot_place_u32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_uint32,
    ]
    lib.vtpu_slot_place_u32.restype = ctypes.c_int64
    lib.vtpu_seg_weighted_count.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.vtpu_lex_bisect16.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.vtpu_otlp_scan.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p,
    ]
    lib.vtpu_otlp_scan.restype = ctypes.c_int
    lib.vtpu_otlp_splice.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p,
    ]
    lib.vtpu_otlp_splice.restype = ctypes.c_int
    lib.vtpu_span_metrics.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
    ]
    return lib


def available() -> bool:
    return _load() is not None


def status() -> dict:
    """Which codec path this process runs, for the status JSON: the
    native library and the file it was loaded from, or the fallbacks."""
    ok = available()
    return {"available": ok, "path": os.path.normpath(_SO) if ok else ""}


# -------------------------------------------------------------- ring tokens
def ring_tokens(tenant: str, trace_ids: list[bytes]) -> np.ndarray:
    """Batch TokenFor: (n,) uint32. Identical to util.hashing.ring_token
    per id; the native fast path requires uniform 16-byte ids (the wire
    canonical form) so both paths hash exactly the same bytes."""
    lib = _load()
    n = len(trace_ids)
    if lib is None or n == 0 or any(len(t) != 16 for t in trace_ids):
        from ..util.hashing import ring_token

        return np.asarray([ring_token(tenant, t) for t in trace_ids], dtype=np.uint32)
    ids = np.frombuffer(b"".join(trace_ids), dtype=np.uint8)
    out = np.zeros(n, dtype=np.uint32)
    tb = tenant.encode()
    lib.vtpu_ring_tokens(tb, len(tb), ids.ctypes.data, 16, n, out.ctypes.data)
    return out


# -------------------------------------------------------------------- bloom
def bloom_add_batch(bloom, trace_ids: list[bytes], k: int) -> bool:
    """Insert ids into a block.bloom.ShardedBloom natively (k = the
    bloom's hash count, passed by the caller so both sides stay in
    sync). Returns False if the caller must fall back to add_many."""
    lib = _load()
    if lib is None or not trace_ids:
        return False
    ids = np.frombuffer(b"".join(trace_ids), dtype=np.uint8)
    lib.vtpu_bloom_add_batch(
        bloom.words.ctypes.data, bloom.n_shards, bloom.words.shape[1],
        bloom.shard_bits, k, ids.ctypes.data, 16, len(trace_ids),
    )
    return True


def bloom_add_ids_array(bloom, ids: np.ndarray, k: int) -> bool:
    """Insert a C-contiguous (n, 16) uint8 id array directly."""
    lib = _load()
    if lib is None or ids.shape[1:] != (16,) or not ids.flags.c_contiguous:
        return False
    lib.vtpu_bloom_add_batch(
        bloom.words.ctypes.data, bloom.n_shards, bloom.words.shape[1],
        bloom.shard_bits, k, ids.ctypes.data, 16, ids.shape[0],
    )
    return True


# --------------------------------------------------------------- wal frames
def varint_frames(data: bytes) -> tuple[np.ndarray, np.ndarray, bool, int] | None:
    """Scan uvarint frames: (body_offsets, body_lengths, clean, torn_at)
    -- torn_at is the file offset of the torn frame's header when not
    clean (len(data) otherwise). None when the native path is missing."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    cap = max(16, len(data) // 2 + 1)
    offs = np.zeros(cap, dtype=np.int64)
    lens = np.zeros(cap, dtype=np.int64)
    r = lib.vtpu_varint_frames(buf.ctypes.data if len(buf) else None, len(data),
                               offs.ctypes.data, lens.ctypes.data, cap)
    clean = r >= 0
    count = r if clean else (-r - 1)
    torn_at = len(data) if clean else int(offs[count])
    return offs[:count], lens[:count], clean, torn_at


# --------------------------------------------------------------------- zstd
# worker 0 runs on the calling thread, so 1 here means "no threads
# spawned at all" -- right on 1-core hosts where extra decode threads
# only add spawn/join and scheduler churn; multi-core hosts keep at
# least 2 workers so batch codecs overlap
_CPUS = os.cpu_count() or 4
_N_THREADS = 1 if _CPUS <= 1 else max(2, _CPUS // 2)


# --------------------------------------------------- snappy / lz4 blocks
# the non-zstd half of the codec matrix: hand-rolled native block codecs
# with the same batch ABI as zstd. Per-codec (bound name, compress name,
# decompress name) -- the worst-case bound comes from the library itself
# so it can never drift from the compressor's actual emission; callers
# fall back to the pure-Python codecs in block/blockcodecs.py when the
# library is absent.
_BLOCK_CODECS = {
    "snappy": ("vtpu_snappy_bound",
               "vtpu_snappy_compress_batch", "vtpu_snappy_decompress_batch"),
    "lz4": ("vtpu_lz4_bound",
            "vtpu_lz4_compress_batch", "vtpu_lz4_decompress_batch"),
}
_DECOMPRESS_RANGES = {
    "zstd": "vtpu_zstd_decompress_batch",
    "snappy": "vtpu_snappy_decompress_batch",
    "lz4": "vtpu_lz4_decompress_batch",
}


def block_compress_chunks(codec: str, chunks: list[bytes]) -> list[bytes] | None:
    """Batch-compress chunks with a non-zstd block codec on native
    threads. None -> caller falls back to the pure-Python codec."""
    lib = _load()
    spec = _BLOCK_CODECS.get(codec)
    n = len(chunks)
    if lib is None or spec is None or n == 0:
        return None
    bound_name, comp_name, _ = spec
    comp = getattr(lib, comp_name, None)
    bound = getattr(lib, bound_name, None)
    if comp is None or bound is None:
        return None
    src = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    in_lens = np.asarray([len(c) for c in chunks], dtype=np.int64)
    in_offs = np.zeros(n, dtype=np.int64)
    np.cumsum(in_lens[:-1], out=in_offs[1:]) if n > 1 else None
    bounds = np.asarray([bound(int(l)) for l in in_lens], dtype=np.int64)
    out_offs = np.zeros(n, dtype=np.int64)
    np.cumsum(bounds[:-1], out=out_offs[1:]) if n > 1 else None
    dst = np.empty(int(bounds.sum()), dtype=np.uint8)
    out_lens = np.zeros(n, dtype=np.int64)
    rc = comp(src.ctypes.data if len(src) else None,
              in_offs.ctypes.data, in_lens.ctypes.data,
              dst.ctypes.data, out_offs.ctypes.data, out_lens.ctypes.data,
              n, _N_THREADS)
    if rc != 0:
        return None
    return [dst[out_offs[i] : out_offs[i] + out_lens[i]].tobytes() for i in range(n)]


def block_decompress_ranges(codec: str, src: np.ndarray, in_offs: np.ndarray,
                            in_lens: np.ndarray, dst: np.ndarray,
                            out_offs: np.ndarray, out_lens: np.ndarray) -> bool:
    """Decompress frames of one contiguous source straight into dst
    positions -- the zstd_decompress_ranges shape generalized over the
    whole codec matrix (the cold pipeline's decode stage dispatches per
    chunk-codec group through this)."""
    lib = _load()
    name = _DECOMPRESS_RANGES.get(codec)
    n = len(in_offs)
    if (lib is None or name is None or n == 0 or src.dtype != np.uint8
            or not src.flags.c_contiguous):
        return False
    fn = getattr(lib, name, None)
    if fn is None:
        return False
    in_offs = np.ascontiguousarray(in_offs, dtype=np.int64)
    in_lens = np.ascontiguousarray(in_lens, dtype=np.int64)
    out_offs = np.ascontiguousarray(out_offs, dtype=np.int64)
    out_lens = np.ascontiguousarray(out_lens, dtype=np.int64)
    rc = fn(src.ctypes.data if len(src) else None,
            in_offs.ctypes.data, in_lens.ctypes.data,
            dst.ctypes.data, out_offs.ctypes.data, out_lens.ctypes.data,
            n, _N_THREADS)
    return rc == 0


def block_decompress_chunks(codec: str, chunks: list[bytes],
                            out_sizes: list[int]) -> list[bytes] | None:
    """Batch-decompress per-chunk bytes with any matrix codec. None ->
    caller falls back to the per-chunk Python decoder."""
    if not chunks:
        return None
    n = len(chunks)
    src = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    in_lens = np.asarray([len(c) for c in chunks], dtype=np.int64)
    in_offs = np.zeros(n, dtype=np.int64)
    np.cumsum(in_lens[:-1], out=in_offs[1:]) if n > 1 else None
    out_lens = np.asarray(out_sizes, dtype=np.int64)
    out_offs = np.zeros(n, dtype=np.int64)
    np.cumsum(out_lens[:-1], out=out_offs[1:]) if n > 1 else None
    dst = np.empty(int(out_lens.sum()), dtype=np.uint8)
    if not block_decompress_ranges(codec, src, in_offs, in_lens, dst, out_offs, out_lens):
        return None
    return [dst[out_offs[i] : out_offs[i] + out_lens[i]].tobytes() for i in range(n)]


def zstd_compress_chunks(chunks: list[bytes], level: int = 3) -> list[bytes] | None:
    if not chunks:
        return None
    n = len(chunks)
    src = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    in_lens = np.asarray([len(c) for c in chunks], dtype=np.int64)
    in_offs = np.zeros(n, dtype=np.int64)
    np.cumsum(in_lens[:-1], out=in_offs[1:]) if n > 1 else None
    return zstd_compress_from(src, in_offs, in_lens, level)


# --------------------------------------------------------- run gather
def gather_runs(src: np.ndarray, dst: np.ndarray, src_offs: np.ndarray,
                dst_offs: np.ndarray, lens: np.ndarray) -> bool:
    """Row-range copies src->dst (both C-contiguous, same dtype/row
    shape): run i moves lens[i] rows from src_offs[i] to dst_offs[i].
    Returns False if the caller must fall back to numpy indexing."""
    lib = _load()
    if lib is None:
        return False
    if not (src.flags.c_contiguous and dst.flags.c_contiguous):
        return False
    itemsize = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    src_offs = np.ascontiguousarray(src_offs, dtype=np.int64)
    dst_offs = np.ascontiguousarray(dst_offs, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int64)
    lib.vtpu_gather_runs(
        src.ctypes.data, dst.ctypes.data,
        src_offs.ctypes.data, dst_offs.ctypes.data, lens.ctypes.data,
        len(src_offs), itemsize,
    )
    return True


def gather_runs_addr(src_addrs: np.ndarray, dst: np.ndarray,
                     dst_offs: np.ndarray, lens: np.ndarray) -> bool:
    """Run copies with per-run absolute source addresses (int64), dst
    offsets/lens in rows: the dst-sequential multi-source merge copy.
    Sources MUST be C-contiguous arrays kept alive by the caller."""
    lib = _load()
    if lib is None or not dst.flags.c_contiguous:
        return False
    itemsize = dst.dtype.itemsize * int(np.prod(dst.shape[1:], dtype=np.int64))
    src_addrs = np.ascontiguousarray(src_addrs, dtype=np.int64)
    dst_offs = np.ascontiguousarray(dst_offs, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int64)
    lib.vtpu_gather_runs_addr(
        src_addrs.ctypes.data, dst.ctypes.data,
        dst_offs.ctypes.data, lens.ctypes.data,
        len(src_addrs), itemsize,
    )
    return True


def gather_runs_remap(src_addrs: np.ndarray, dst: np.ndarray,
                      dst_offs: np.ndarray, lens: np.ndarray,
                      remap_addrs: np.ndarray, remap_lens: np.ndarray) -> bool:
    """gather_runs_addr fused with an int32 code remap (per-run remap
    table address + length; negative codes pass through). Returns False
    when the caller must redo via its checked fallback -- including
    out-of-range codes (corrupt input), which the kernel refuses to
    read past."""
    lib = _load()
    if lib is None or dst.dtype != np.int32 or not dst.flags.c_contiguous:
        return False
    src_addrs = np.ascontiguousarray(src_addrs, dtype=np.int64)
    dst_offs = np.ascontiguousarray(dst_offs, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int64)
    remap_addrs = np.ascontiguousarray(remap_addrs, dtype=np.int64)
    remap_lens = np.ascontiguousarray(remap_lens, dtype=np.int64)
    oob = lib.vtpu_gather_runs_remap(
        src_addrs.ctypes.data, dst.ctypes.data,
        dst_offs.ctypes.data, lens.ctypes.data,
        remap_addrs.ctypes.data, remap_lens.ctypes.data,
        len(src_addrs),
    )
    return oob == 0


# --------------------------------------------------- zstd into-buffer
def zstd_decompress_into(chunks: list[bytes], dst: np.ndarray,
                         out_offs: np.ndarray, out_lens: np.ndarray) -> bool:
    """Batch-decompress chunks straight into caller-provided positions of
    one destination buffer (uint8) -- no per-chunk bytes objects, no
    joins. Returns False -> caller falls back."""
    n = len(chunks)
    if _load() is None or n == 0:
        return False
    src = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    in_lens = np.asarray([len(c) for c in chunks], dtype=np.int64)
    in_offs = np.zeros(n, dtype=np.int64)
    np.cumsum(in_lens[:-1], out=in_offs[1:]) if n > 1 else None
    return zstd_decompress_ranges(src, in_offs, in_lens, dst, out_offs, out_lens)


def zstd_decompress_ranges(src: np.ndarray, in_offs: np.ndarray,
                           in_lens: np.ndarray, dst: np.ndarray,
                           out_offs: np.ndarray, out_lens: np.ndarray) -> bool:
    """Decompress frames at in_offs/in_lens of one contiguous source
    buffer into out_offs/out_lens of dst. The zero-copy shape: callers
    that fetch a column's adjacent chunks with ONE ranged read pass the
    buffer straight through (no per-chunk bytes, no join)."""
    lib = _load()
    n = len(in_offs)
    if lib is None or n == 0 or src.dtype != np.uint8 or not src.flags.c_contiguous:
        return False
    # bind conversions to locals: .ctypes.data of an expression temporary
    # can be freed before the foreign call runs (dangling pointer)
    in_offs = np.ascontiguousarray(in_offs, dtype=np.int64)
    in_lens = np.ascontiguousarray(in_lens, dtype=np.int64)
    out_offs = np.ascontiguousarray(out_offs, dtype=np.int64)
    out_lens = np.ascontiguousarray(out_lens, dtype=np.int64)
    rc = lib.vtpu_zstd_decompress_batch(
        src.ctypes.data if len(src) else None,
        in_offs.ctypes.data, in_lens.ctypes.data,
        dst.ctypes.data,
        out_offs.ctypes.data, out_lens.ctypes.data,
        n, _N_THREADS,
    )
    return rc == 0


def zstd_compress_from(buf: np.ndarray, in_offs: np.ndarray, in_lens: np.ndarray,
                       level: int = 3) -> list[bytes] | None:
    """Batch-compress ranges of an existing contiguous buffer (uint8
    view) without materializing per-chunk source bytes."""
    lib = _load()
    n = len(in_offs)
    if lib is None or n == 0:
        return None
    in_offs = np.ascontiguousarray(in_offs, dtype=np.int64)
    in_lens = np.ascontiguousarray(in_lens, dtype=np.int64)
    # ZSTD_compressBound(n) = n + n/256 + small margin; computing it
    # vectorized (with extra slack) avoids one ctypes call per chunk
    bounds = in_lens + (in_lens >> 8) + 1024
    out_offs = np.zeros(n, dtype=np.int64)
    np.cumsum(bounds[:-1], out=out_offs[1:]) if n > 1 else None
    dst = np.empty(int(bounds.sum()), dtype=np.uint8)
    out_lens = np.zeros(n, dtype=np.int64)
    rc = lib.vtpu_zstd_compress_batch(
        buf.ctypes.data, in_offs.ctypes.data, in_lens.ctypes.data,
        dst.ctypes.data, out_offs.ctypes.data, out_lens.ctypes.data,
        n, level, _N_THREADS,
    )
    if rc != 0:
        return None
    return [dst[out_offs[i] : out_offs[i] + out_lens[i]].tobytes() for i in range(n)]


# ---------------------------------------------------------- dict union
def dict_union(raws: list[tuple[bytes, np.ndarray]]):
    """K-way merge of K sorted dictionaries given as (blob, u32 offsets)
    pairs (block.dictionary.Dictionary.raw()). Returns (merged_blob,
    merged_offsets, [per-source int32 remap]). Pure-numpy fallback when
    the native library is absent."""
    n_src = len(raws)
    counts = np.asarray([len(offs) - 1 for _, offs in raws], dtype=np.int64)
    total = int(counts.sum())
    if total == 0:
        return b"", np.zeros(1, dtype=np.uint32), [np.zeros(0, np.int32) for _ in raws]
    lib = _load()
    if lib is None:
        return _dict_union_py(raws, counts)
    all_offsets = np.concatenate([
        np.ascontiguousarray(offs, dtype=np.uint32) for _, offs in raws
    ])
    off_starts = np.zeros(n_src, dtype=np.int64)
    np.cumsum(counts[:-1] + 1, out=off_starts[1:]) if n_src > 1 else None
    blobs = b"".join(b for b, _ in raws)
    blob_lens = np.asarray([len(b) for b, _ in raws], dtype=np.int64)
    blob_starts = np.zeros(n_src, dtype=np.int64)
    np.cumsum(blob_lens[:-1], out=blob_starts[1:]) if n_src > 1 else None
    all_blobs = np.frombuffer(blobs, dtype=np.uint8)
    out_offsets = np.zeros(total + 1, dtype=np.uint32)
    out_blob = np.zeros(max(1, len(blobs)), dtype=np.uint8)
    remap_flat = np.zeros(total, dtype=np.int32)
    remap_starts = np.zeros(n_src, dtype=np.int64)
    np.cumsum(counts[:-1], out=remap_starts[1:]) if n_src > 1 else None
    out_blob_len = np.zeros(1, dtype=np.int64)
    n_out = lib.vtpu_dict_union(
        n_src, counts.ctypes.data, all_offsets.ctypes.data, off_starts.ctypes.data,
        all_blobs.ctypes.data if len(all_blobs) else None, blob_starts.ctypes.data,
        out_offsets.ctypes.data, out_blob.ctypes.data,
        remap_flat.ctypes.data, remap_starts.ctypes.data, out_blob_len.ctypes.data,
    )
    if n_out < 0:
        return _dict_union_py(raws, counts)
    merged_blob = out_blob[: int(out_blob_len[0])].tobytes()
    merged_offsets = out_offsets[: n_out + 1].copy()
    remaps = [
        remap_flat[remap_starts[i] : remap_starts[i] + counts[i]].copy()
        for i in range(n_src)
    ]
    return merged_blob, merged_offsets, remaps


def _dict_union_py(raws, counts):
    """Fallback: bytes-level set union + searchsorted remap."""
    per_src: list[list[bytes]] = []
    for blob, offs in raws:
        o = offs.tolist()
        per_src.append([blob[o[i] : o[i + 1]] for i in range(len(o) - 1)])
    merged = sorted(set().union(*[set(s) for s in per_src])) if per_src else []
    code_of = {s: i for i, s in enumerate(merged)}
    remaps = [
        np.asarray([code_of[s] for s in src], dtype=np.int32) for src in per_src
    ]
    blob = b"".join(merged)
    offs = np.zeros(len(merged) + 1, dtype=np.uint32)
    if merged:
        np.cumsum([len(s) for s in merged], out=offs[1:])
    return blob, offs, remaps


# --------------------------------------------------------- search eval
# op codes mirror native's CMP_* enum; hostfilter maps its op strings here
CMP_CODES = {"eq": 0, "ne": 1, "lt": 2, "le": 3, "gt": 4, "ge": 5,
             "range": 6, "ne_present": 7}


def mask_cmp(x: np.ndarray, op: str, a: int, b: int = 0) -> np.ndarray | None:
    """Single-pass comparison mask (uint8 0/1) over an int32/int64
    column. None -> caller falls back to numpy."""
    lib = _load()
    code = CMP_CODES.get(op)
    if lib is None or code is None or not x.flags.c_contiguous or x.ndim != 1:
        return None
    out = np.empty(x.shape[0], dtype=np.uint8)
    if x.dtype == np.int32:
        lib.vtpu_mask_cmp_i32(x.ctypes.data, x.shape[0], code, int(a), int(b),
                              out.ctypes.data)
    elif x.dtype == np.int64:
        lib.vtpu_mask_cmp_i64(x.ctypes.data, x.shape[0], code, int(a), int(b),
                              out.ctypes.data)
    else:
        return None
    return out


def mask_lut(idx: np.ndarray, lut: np.ndarray) -> np.ndarray | None:
    """out[j] = lut[idx[j]] with negative/out-of-range idx -> 0: the
    res-table -> span mask gather in one pass."""
    lib = _load()
    if (lib is None or idx.dtype != np.int32 or not idx.flags.c_contiguous
            or lut.dtype != np.uint8 or not lut.flags.c_contiguous):
        return None
    out = np.empty(idx.shape[0], dtype=np.uint8)
    lib.vtpu_mask_lut_i32(idx.ctypes.data, idx.shape[0], lut.ctypes.data,
                          lut.shape[0], out.ctypes.data)
    return out


def seg_count_mask(mask: np.ndarray, span_off: np.ndarray,
                   n_spans: int) -> np.ndarray | None:
    """Per-trace count of set mask bytes: out[t] = sum(mask[off[t]:off[t+1]])
    with offsets clipped to n_spans. mask may be bool or uint8."""
    lib = _load()
    if lib is None or span_off.dtype != np.int32 or not span_off.flags.c_contiguous:
        return None
    if mask.dtype == np.bool_:
        mask = mask.view(np.uint8)
    if mask.dtype != np.uint8 or not mask.flags.c_contiguous:
        return None
    n_traces = span_off.shape[0] - 1
    out = np.empty(n_traces, dtype=np.int32)
    lib.vtpu_seg_count_mask(mask.ctypes.data, span_off.ctypes.data,
                            n_traces, n_spans, out.ctypes.data)
    return out


def seg_weighted_count(mask: np.ndarray, weights: np.ndarray,
                       span_off: np.ndarray, n_spans: int) -> np.ndarray | None:
    """Weighted per-segment fold: out[t] = sum(weights[j] for j in
    off[t]:off[t+1] where mask[j]), offsets clipped to n_spans. The tres
    membership axis' matched-span counter (weights = entry span counts);
    replaces the pad+reduceat numpy path at ~5x the speed."""
    lib = _load()
    if lib is None or getattr(lib, "vtpu_seg_weighted_count", None) is None:
        return None
    if span_off.dtype != np.int32 or not span_off.flags.c_contiguous:
        return None
    if mask.dtype == np.bool_:
        mask = mask.view(np.uint8)
    if (mask.dtype != np.uint8 or not mask.flags.c_contiguous
            or weights.dtype != np.int32 or not weights.flags.c_contiguous):
        return None
    n_traces = span_off.shape[0] - 1
    out = np.empty(n_traces, dtype=np.int64)
    lib.vtpu_seg_weighted_count(mask.ctypes.data, weights.ctypes.data,
                                span_off.ctypes.data, n_traces, n_spans,
                                out.ctypes.data)
    return out


def slot_place(owners: np.ndarray, span_base: int, n_spans: int, n_spans_b: int,
               k: int, src: np.ndarray, out: np.ndarray, pad) -> bool:
    """One generic-attribute value column placed slot-major into `out`
    (k planes of n_spans_b, then the overflow rows; every element
    written once, `pad` where no row lands): the native pass of
    ops/stage._SlotLayout.place, GIL released for its length. `owners`
    are the rows' span rows, ascending; `src` and `out` share a 4-byte
    dtype. False -> `out` holds nothing to keep and the caller places
    with numpy."""
    lib = _load()
    if (lib is None or owners.dtype != np.int32 or src.dtype.itemsize != 4
            or out.dtype != src.dtype or src.ndim != 1 or out.ndim != 1
            or src.shape != owners.shape
            or not (owners.flags.c_contiguous and src.flags.c_contiguous
                    and out.flags.c_contiguous)):
        return False
    pad_bits = int(np.asarray(pad, dtype=src.dtype).view(np.uint32))
    return lib.vtpu_slot_place_u32(
        owners.ctypes.data, owners.shape[0], span_base, n_spans, n_spans_b, k,
        src.ctypes.data, out.ctypes.data, out.shape[0], pad_bits) >= 0


def lex_bisect16(ids: np.ndarray, queries: np.ndarray) -> np.ndarray | None:
    """Exact-match rows of 16-byte queries in a sorted (n, 16) id
    table (-1 miss). ids/queries: uint8, C-contiguous."""
    lib = _load()
    if lib is None or getattr(lib, "vtpu_lex_bisect16", None) is None:
        return None
    if (ids.dtype != np.uint8 or queries.dtype != np.uint8
            or ids.ndim != 2 or ids.shape[1] != 16
            or queries.ndim != 2 or queries.shape[1] != 16
            or not ids.flags.c_contiguous or not queries.flags.c_contiguous):
        return None
    q = queries.shape[0]
    out = np.empty(q, dtype=np.int32)
    lib.vtpu_lex_bisect16(ids.ctypes.data, ids.shape[0],
                          queries.ctypes.data, q, out.ctypes.data)
    return out


def otlp_scan(payload: bytes):
    """Structural scan of OTLP trace bytes (vtpu_otlp_scan): returns
    (span_off, span_len, span_rs, span_ss, trace_ids (n,16) u8,
    start_ns, end_ns, env_buf bytes, senv_buf bytes, rs_env (off,len),
    ss_env (off,len,rs)) or None (native unavailable / malformed
    payload -- caller decodes via the Python model path)."""
    lib = _load()
    if lib is None or getattr(lib, "vtpu_otlp_scan", None) is None:
        return None
    n = len(payload)
    if n == 0:
        return None
    buf = np.frombuffer(payload, dtype=np.uint8)
    # a span submessage can't be smaller than ~20 bytes (16B trace id +
    # framing); start generous, regrow on rc=2
    cap_spans = max(16, n // 24 + 8)
    cap_rs = cap_ss = max(8, n // 64 + 8)
    for _ in range(4):
        span_off = np.empty(cap_spans, np.int64)
        span_len = np.empty(cap_spans, np.int64)
        span_rs = np.empty(cap_spans, np.int32)
        span_ss = np.empty(cap_spans, np.int32)
        tids = np.empty((cap_spans, 16), np.uint8)
        start_ns = np.empty(cap_spans, np.uint64)
        end_ns = np.empty(cap_spans, np.uint64)
        env = np.empty(n + 16, np.uint8)
        senv = np.empty(n + 16, np.uint8)
        rs_off = np.empty(cap_rs, np.int64)
        rs_len = np.empty(cap_rs, np.int64)
        ss_off = np.empty(cap_ss, np.int64)
        ss_len = np.empty(cap_ss, np.int64)
        ss_rs = np.empty(cap_ss, np.int32)
        counts = np.zeros(5, np.int64)
        rc = lib.vtpu_otlp_scan(
            buf.ctypes.data, n,
            span_off.ctypes.data, span_len.ctypes.data, span_rs.ctypes.data,
            span_ss.ctypes.data, tids.ctypes.data, start_ns.ctypes.data,
            end_ns.ctypes.data, cap_spans,
            env.ctypes.data, env.shape[0],
            senv.ctypes.data, senv.shape[0],
            rs_off.ctypes.data, rs_len.ctypes.data, cap_rs,
            ss_off.ctypes.data, ss_len.ctypes.data, ss_rs.ctypes.data, cap_ss,
            counts.ctypes.data,
        )
        if rc == 2:
            cap_spans *= 4
            cap_rs *= 4
            cap_ss *= 4
            continue
        if rc != 0:
            return None
        k, nrs, nss = int(counts[0]), int(counts[1]), int(counts[2])
        return (span_off[:k], span_len[:k], span_rs[:k], span_ss[:k],
                tids[:k], start_ns[:k], end_ns[:k],
                env[: int(counts[3])].tobytes(),
                senv[: int(counts[4])].tobytes(),
                rs_off[:nrs], rs_len[:nrs],
                ss_off[:nss], ss_len[:nss], ss_rs[:nss])
    return None


def otlp_splice(payload: bytes):
    """Scan + group-by-trace + emit finished wire segments, ONE native
    call (vtpu_otlp_splice): returns (tids (K,16) u8, seg_off (K,),
    seg_len (K,), start_s (K,), end_s (K,), out u8 buffer, n_spans) or
    None (native unavailable / malformed -- caller uses the Python
    path). Each out[seg_off[u] : seg_off[u]+seg_len[u]] is a complete
    segment (9B header + per-trace TracesData)."""
    lib = _load()
    if lib is None or getattr(lib, "vtpu_otlp_splice", None) is None:
        return None
    n = len(payload)
    if n == 0:
        return None
    buf = np.frombuffer(payload, dtype=np.uint8)
    # envelopes repeat per trace, so output can exceed the payload;
    # 2n + slack covers typical shapes, rc=2 reports the exact need
    cap_out = 2 * n + 4096
    cap_tr = max(16, n // 64 + 8)
    for _ in range(3):
        out = np.empty(cap_out, np.uint8)
        tids = np.empty((cap_tr, 16), np.uint8)
        seg_off = np.empty(cap_tr, np.int64)
        seg_len = np.empty(cap_tr, np.int64)
        st = np.empty(cap_tr, np.int64)
        en = np.empty(cap_tr, np.int64)
        counts = np.zeros(3, np.int64)
        rc = lib.vtpu_otlp_splice(
            buf.ctypes.data, n, out.ctypes.data, cap_out,
            tids.ctypes.data, cap_tr,
            seg_off.ctypes.data, seg_len.ctypes.data,
            st.ctypes.data, en.ctypes.data, counts.ctypes.data,
        )
        if rc == 2:
            cap_tr = max(cap_tr * 2, int(counts[0]))
            cap_out = max(cap_out * 2, int(counts[1]))
            continue
        if rc != 0:
            return None
        K = int(counts[0])
        return (tids[:K], seg_off[:K], seg_len[:K], st[:K], en[:K], out,
                int(counts[2]))
    return None


def span_metrics_fold(sid: np.ndarray, dur: np.ndarray, edges: np.ndarray,
                      n_series: int):
    """Fused histogram + latency-sum fold: returns (hist (S, nb) i64,
    lat_sum (S,) f64) or None -> numpy fallback. Buckets match
    np.searchsorted(edges, dur) ('left')."""
    lib = _load()
    if (lib is None or sid.dtype != np.int32 or not sid.flags.c_contiguous
            or dur.dtype != np.float32 or not dur.flags.c_contiguous):
        return None
    edges = np.ascontiguousarray(edges, dtype=np.float32)
    nb = edges.shape[0] + 1
    hist = np.zeros((n_series, nb), dtype=np.int64)
    lat_sum = np.zeros(n_series, dtype=np.float64)
    lib.vtpu_span_metrics(
        sid.ctypes.data, dur.ctypes.data, sid.shape[0],
        edges.ctypes.data, edges.shape[0], n_series,
        hist.ctypes.data, lat_sum.ctypes.data,
    )
    return hist, lat_sum


def zstd_decompress_chunks(chunks: list[bytes], out_sizes: list[int]) -> list[bytes] | None:
    if not chunks:
        return None
    n = len(chunks)
    out_lens = np.asarray(out_sizes, dtype=np.int64)
    out_offs = np.zeros(n, dtype=np.int64)
    np.cumsum(out_lens[:-1], out=out_offs[1:]) if n > 1 else None
    dst = np.zeros(int(out_lens.sum()), dtype=np.uint8)
    if not zstd_decompress_into(chunks, dst, out_offs, out_lens):
        return None
    return [dst[out_offs[i]: out_offs[i] + out_lens[i]].tobytes() for i in range(n)]
