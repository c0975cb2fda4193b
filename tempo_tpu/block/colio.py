"""Column blob IO: named numpy arrays in one backend object, chunked by
row group.

Layout: [chunk buffers, each independently zstd-compressed] [footer JSON]
[uint32le footer len] [magic 'VTPU'].

Every column belongs to an *axis* (span rows, trace rows, attr rows, ...)
and is stored as one compressed chunk per row group along that axis. The
footer maps column name -> dtype/shape/axis/chunk table, so a reader can
fetch the footer with two small range reads and then range-read only the
(column, row-group) chunks a query touches -- the role parquet column
chunks + pages play for the reference (vparquet block_search.go,
parquetquery), but deserializing straight into flat device-uploadable
arrays with zero transposition.
"""

from __future__ import annotations

import json
import struct
import threading
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

try:
    import zstandard
except ModuleNotFoundError:  # image without the wheel: zlib-backed shim
    from ..util import zstdshim as zstandard

MAGIC = b"VTPU"
_TAIL = struct.Struct("<I4s")

CODEC_RAW = "raw"
CODEC_ZSTD = "zstd"
# constant chunk: stored bytes are ONE row, tiled to raw_len at read.
# The structural win parquet gets from RLE/dictionary pages: absent
# optional columns (http_*, sentinel ids, unused sattr typed lanes) are
# roughly half a realistic block's raw bytes, and with this codec they
# cost one row of storage, zero compression, zero decompression, and --
# via stride-0 broadcast views on the compaction path -- zero copies.
CODEC_CONST = "const"
_MIN_COMPRESS = 128
_CONST_MIN = 64  # don't bother const-marking chunks smaller than this

# codec matrix (reference: tempodb/backend/encoding.go's nine codecs).
# zstd is the default; snappy and lz4 (block/blockcodecs.py) are the
# speed tier with native threaded batch paths next to the zstd ones and
# pure-Python fallbacks; the stdlib codecs (gzip/lzma) trade ratio/CPU
# for interop. Decode always dispatches on the chunk's recorded codec,
# so blocks written with any codec stay readable.


def is_broadcast(arr: np.ndarray) -> bool:
    """True for stride-0 first-dim views (np.broadcast_to of one row) --
    the in-memory marker for "this column is constant". The single
    definition of the convention; the compaction merge imports it."""
    return arr.ndim >= 1 and arr.size > 0 and arr.strides[0] == 0


def _gzip_c(data: bytes, level: int) -> bytes:
    import gzip

    # mtime=0 keeps output deterministic (chunk bytes are content-addressed
    # by tests and dedupe-friendly in object stores)
    return gzip.compress(data, compresslevel=min(level, 9), mtime=0)


def _gzip_d(data: bytes, raw_len: int) -> bytes:
    import zlib

    # wbits=47 auto-detects gzip (RFC1952) and zlib (RFC1950) framing:
    # blocks written before the codec emitted true gzip used zlib framing
    return zlib.decompress(data, 47)


def _lzma_c(data: bytes, level: int) -> bytes:
    import lzma

    return lzma.compress(data, preset=min(level, 6))


def _lzma_d(data: bytes, raw_len: int) -> bytes:
    import lzma

    return lzma.decompress(data)


def _snappy_c(data: bytes, level: int) -> bytes:
    from .blockcodecs import snappy_compress

    return snappy_compress(data)  # snappy has no levels


def _snappy_d(data: bytes, raw_len: int) -> bytes:
    from .blockcodecs import snappy_decompress

    return snappy_decompress(data, raw_len)


def _lz4_c(data: bytes, level: int) -> bytes:
    from .blockcodecs import lz4_compress

    return lz4_compress(data)  # lz4 block format has no levels


def _lz4_d(data: bytes, raw_len: int) -> bytes:
    from .blockcodecs import lz4_decompress

    return lz4_decompress(data, raw_len)


_EXTRA_CODECS: dict[str, tuple] = {  # name -> (compress(data, level), decompress)
    "gzip": (_gzip_c, _gzip_d),
    "lzma": (_lzma_c, _lzma_d),
    "snappy": (_snappy_c, _snappy_d),
    "lz4": (_lz4_c, _lz4_d),
}
# codecs whose chunk batches the native layer can decompress in one
# threaded ranges call (the cold pipeline's decode stage); everything
# else decodes per chunk through _EXTRA_CODECS
_NATIVE_RANGE_CODECS = frozenset({CODEC_ZSTD, "snappy", "lz4"})


class AxisChunks:
    """Row boundaries of the row groups along one axis: offsets[g] ..
    offsets[g+1] are the rows of group g."""

    def __init__(self, offsets: list[int]):
        assert len(offsets) >= 2 and offsets[0] == 0
        self.offsets = list(offsets)

    @property
    def n_groups(self) -> int:
        return len(self.offsets) - 1

    @property
    def n_rows(self) -> int:
        return self.offsets[-1]


def pack_columns_stream(
    cols: dict[str, np.ndarray],
    axes: dict[str, AxisChunks] | None = None,
    col_axis: dict[str, str] | None = None,
    level: int = 3,
    codec: str = CODEC_ZSTD,
    level_for=None,
    footer: str = "binary",
):
    """Yield the serialized pack as byte parts, ONE COLUMN AT A TIME
    (chunks of a column compress as one threaded native batch, then the
    footer+tail last). Peak memory is a single column's chunks, so the
    streamed-flush write path (backend appender) never buffers the whole
    block -- the role of the reference's incremental backend.Append
    tracker (v2/streaming_block.go:13-90)."""
    axes = axes or {}
    col_axis = col_axis or {}
    if codec not in (CODEC_ZSTD, CODEC_RAW) and codec not in _EXTRA_CODECS:
        raise ValueError(
            f"unknown codec {codec!r} (matrix: "
            f"{[CODEC_RAW, CODEC_ZSTD, *sorted(_EXTRA_CODECS)]})"
        )
    footer_tbl: dict = {"cols": {}, "axes": {k: v.offsets for k, v in axes.items()}}
    offset = 0

    from ..native import zstd_compress_from

    for name, arr in cols.items():
        # per-column override (level_for(name) -> int | "raw" | None):
        # ints pick a zstd level; "raw" stores the column uncompressed
        # (the fast-decode policy for metadata axes a cold query must
        # decode, block/builder.FAST_DECODE_PREFIXES); None keeps the
        # pack-wide level
        col_level = level
        col_raw = False
        if level_for is not None and codec == CODEC_ZSTD:
            # zstd only: the stdlib codec matrix rejects the overrides
            ov = level_for(name)
            if ov == "raw":  # store uncompressed (fast-decode policy)
                col_raw = True
            elif ov is not None:
                col_level = ov
        # stride-0 first dim = a broadcast view (read_all broadcast_const
        # / the compaction merge's const fast path): constant by
        # construction, and materializing it here would defeat the point.
        # codec == raw means "store bytes verbatim", so raw packs
        # materialize broadcast inputs instead of emitting const chunks
        # (matching the sampled detector's raw-codec skip below).
        bcast = codec != CODEC_RAW and is_broadcast(arr)
        if not bcast:
            arr = np.ascontiguousarray(arr)
        axis = col_axis.get(name)
        row_bytes = arr.dtype.itemsize * int(np.prod(arr.shape[1:], dtype=np.int64))
        if axis is not None:
            ax = axes[axis]
            if ax.n_rows != arr.shape[0]:
                raise ValueError(
                    f"column {name}: {arr.shape[0]} rows != axis {axis} ({ax.n_rows})"
                )
            bounds = [(ax.offsets[g] * row_bytes, ax.offsets[g + 1] * row_bytes)
                      for g in range(ax.n_groups)]
        else:
            bounds = [(0, arr.shape[0] * row_bytes)]

        if bcast:
            row = np.ascontiguousarray(arr[:1]).tobytes()
            recs = []
            for lo, hi in bounds:
                raw_len = hi - lo
                if raw_len == 0:
                    recs.append([offset, 0, 0, CODEC_RAW])
                    continue
                recs.append([offset, len(row), raw_len, CODEC_CONST])
                offset += len(row)
                yield row
            footer_tbl["cols"][name] = {
                "dtype": str(arr.dtype),
                "shape": list(arr.shape),
                "axis": axis,
                "chunks": recs,
            }
            continue

        buf = arr.reshape(-1).view(np.uint8) if arr.size else np.empty(0, np.uint8)

        # constant-chunk detection: a cheap sampled bail (rows 1 and mid
        # vs row 0 -- random data fails in nanoseconds) gates the full
        # equality check, so only genuinely constant chunks pay a read
        # pass. Skipped for raw packs (codec == raw means "store bytes
        # verbatim").
        const_rows: dict[int, bytes] = {}
        if codec != CODEC_RAW and row_bytes > 0:
            for i, (lo, hi) in enumerate(bounds):
                ln = hi - lo
                if ln < max(_CONST_MIN, 2 * row_bytes):
                    continue
                r0 = buf[lo : lo + row_bytes]
                mid = lo + ((ln // row_bytes) // 2) * row_bytes
                if not ((buf[lo + row_bytes : lo + 2 * row_bytes] == r0).all()
                        and (buf[mid : mid + row_bytes] == r0).all()):
                    continue
                if (buf[lo:hi].reshape(-1, row_bytes) == r0).all():
                    const_rows[i] = r0.tobytes()

        # compress this column's compressible chunks: zstd runs as one
        # threaded native batch STRAIGHT FROM the array's memory (no
        # per-chunk source copies, python zstd as fallback); the stdlib
        # codec matrix handles the rest per chunk
        to_compress = [i for i, (lo, hi) in enumerate(bounds)
                       if hi - lo >= _MIN_COMPRESS and codec != CODEC_RAW
                       and not col_raw and i not in const_rows]
        compressed: dict[int, bytes] = {}
        if to_compress and codec == CODEC_ZSTD:
            outs = zstd_compress_from(
                buf,
                np.asarray([bounds[i][0] for i in to_compress], np.int64),
                np.asarray([bounds[i][1] - bounds[i][0] for i in to_compress], np.int64),
                col_level,
            )
            if outs is None:
                comp = zstandard.ZstdCompressor(level=col_level)
                outs = [comp.compress(buf[bounds[i][0] : bounds[i][1]].tobytes())
                        for i in to_compress]
            compressed = dict(zip(to_compress, outs))
        elif to_compress:
            cfun = _EXTRA_CODECS[codec][0]  # unknown codec fails loudly here
            outs = None
            if codec in _NATIVE_RANGE_CODECS:
                # snappy/lz4: one threaded native batch for the column's
                # chunks, exactly like the zstd path above
                from ..native import block_compress_chunks

                outs = block_compress_chunks(
                    codec,
                    [buf[bounds[i][0] : bounds[i][1]].tobytes() for i in to_compress])
            if outs is not None:
                compressed = dict(zip(to_compress, outs))
            else:
                compressed = {
                    i: cfun(buf[bounds[i][0] : bounds[i][1]].tobytes(), col_level)
                    for i in to_compress
                }

        recs: list[list] = []
        for i, (lo, hi) in enumerate(bounds):
            raw_len = hi - lo
            row = const_rows.get(i)
            z = compressed.get(i)
            if row is not None:
                data, chunk_codec = row, CODEC_CONST
            elif z is not None and len(z) < raw_len:
                data, chunk_codec = z, codec
            else:
                data, chunk_codec = buf[lo:hi].tobytes(), CODEC_RAW
            recs.append([offset, len(data), raw_len, chunk_codec])
            offset += len(data)
            yield data
        footer_tbl["cols"][name] = {
            "dtype": str(arr.dtype),
            "shape": list(arr.shape),
            "axis": axis,
            "chunks": recs,
        }

    # footer="json" writes the vtpu1-era footer (block version
    # compatibility: the convert tool and mixed-version tests produce
    # genuinely old-format blocks); readers auto-detect either form
    fbytes = (_encode_footer_binary(footer_tbl) if footer == "binary"
              else json.dumps(footer_tbl, separators=(",", ":")).encode("utf-8"))
    yield fbytes
    yield _TAIL.pack(len(fbytes), MAGIC)


# Binary footer ("\x00BF1" marker; JSON can never start with NUL): the
# JSON footer cost ~0.8 ms to parse per cold block open -- a fixed tax
# on every one-shot reader. Encoding: marker, then [axes] u32 count +
# per axis (u16 name len, name utf8, u32 n_offsets, i64 offsets), then
# [cols] u32 count + per column (u16 name len, name, u8 dtype len,
# dtype str, u8 ndim, i64 dims, u8 axis len, axis, u32 n_chunks, chunks
# as (n,3) i64 [off, stored, raw] + n bytes codec indexes into the u8
# codec table emitted before [cols]). Readers accept both forms.
_BF_MARKER = b"\x00BF1"


def _encode_footer_binary(footer: dict) -> bytes:
    out = bytearray(_BF_MARKER)

    def put_str(s: str, wide: bool = False):
        b = s.encode("utf-8")
        out.extend(struct.pack("<H" if wide else "<B", len(b)))
        out.extend(b)

    axes = footer.get("axes", {})
    out.extend(struct.pack("<I", len(axes)))
    for name, offsets in axes.items():
        put_str(name, wide=True)
        arr = np.asarray(offsets, dtype=np.int64)
        out.extend(struct.pack("<I", arr.shape[0]))
        out.extend(arr.tobytes())
    codecs = sorted({rec[3] for c in footer["cols"].values() for rec in c["chunks"]})
    out.extend(struct.pack("<B", len(codecs)))
    for c in codecs:
        put_str(c)
    cidx = {c: i for i, c in enumerate(codecs)}
    cols = footer["cols"]
    out.extend(struct.pack("<I", len(cols)))
    for name, meta in cols.items():
        put_str(name, wide=True)
        body = bytearray()

        def bput_str(s: str):
            b = s.encode("utf-8")
            body.extend(struct.pack("<B", len(b)))
            body.extend(b)

        bput_str(meta["dtype"])
        shape = meta["shape"]
        body.extend(struct.pack("<B", len(shape)))
        body.extend(np.asarray(shape, dtype=np.int64).tobytes())
        bput_str(meta["axis"] or "")
        recs = meta["chunks"]
        body.extend(struct.pack("<I", len(recs)))
        tbl = np.asarray([[r[0], r[1], r[2]] for r in recs], dtype=np.int64)
        body.extend(tbl.tobytes())
        body.extend(bytes(cidx[r[3]] for r in recs))
        # body-length prefix: a reader indexes all columns by skipping
        # bodies in one hop each, decoding only the columns it touches
        out.extend(struct.pack("<I", len(body)))
        out.extend(body)
    return bytes(out)


class _LazyFooterCols(dict):
    """Footer column table decoding each column's chunk records on first
    access: a cold query touches ~a dozen of the pack's ~90 columns, so
    eagerly building every chunk list cost more than the whole footer
    read. Maps name -> meta dict; undecoded entries hold their body's
    byte range in the footer buffer."""

    def __init__(self, data: bytes, codecs: list[str], index: dict[str, tuple[int, int]]):
        super().__init__()
        self._data = data
        self._codecs = codecs
        self._index = index
        for name in index:
            dict.__setitem__(self, name, None)

    def _decode(self, name: str) -> dict:
        data, pos = self._data, self._index[name][0]
        (dlen,) = struct.unpack_from("<B", data, pos)
        pos += 1
        dtype = data[pos : pos + dlen].decode("utf-8")
        pos += dlen
        (ndim,) = struct.unpack_from("<B", data, pos)
        pos += 1
        shape = np.frombuffer(data, dtype=np.int64, count=ndim, offset=pos).tolist()
        pos += 8 * ndim
        (alen,) = struct.unpack_from("<B", data, pos)
        pos += 1
        axis = data[pos : pos + alen].decode("utf-8") or None
        pos += alen
        (n_chunks,) = struct.unpack_from("<I", data, pos)
        pos += 4
        tbl = np.frombuffer(data, dtype=np.int64, count=3 * n_chunks, offset=pos)
        pos += 24 * n_chunks
        ci = data[pos : pos + n_chunks]
        codecs = self._codecs
        meta = {
            "dtype": dtype,
            "shape": shape,
            "axis": axis,
            "chunks": [[o, s, r, codecs[c]]
                       for (o, s, r), c in zip(tbl.reshape(-1, 3).tolist(), ci)],
        }
        dict.__setitem__(self, name, meta)
        return meta

    def __getitem__(self, name: str) -> dict:
        v = dict.__getitem__(self, name)
        return self._decode(name) if v is None else v

    def get(self, name, default=None):
        return self[name] if name in self else default

    def items(self):
        return [(k, self[k]) for k in self.keys()]

    def values(self):
        return [self[k] for k in self.keys()]


def _decode_footer_binary(data: bytes) -> dict:
    pos = len(_BF_MARKER)

    def get(fmt):
        nonlocal pos
        vals = struct.unpack_from(fmt, data, pos)
        pos += struct.calcsize(fmt)
        return vals

    def get_str(wide: bool = False) -> str:
        nonlocal pos
        (ln,) = get("<H" if wide else "<B")
        s = data[pos : pos + ln].decode("utf-8")
        pos += ln
        return s

    axes = {}
    (n_axes,) = get("<I")
    for _ in range(n_axes):
        name = get_str(wide=True)
        (n_off,) = get("<I")
        offs = np.frombuffer(data, dtype=np.int64, count=n_off, offset=pos)
        pos += 8 * n_off
        axes[name] = offs.tolist()
    (n_codecs,) = get("<B")
    codecs = [get_str() for _ in range(n_codecs)]
    index: dict[str, tuple[int, int]] = {}
    (n_cols,) = get("<I")
    for _ in range(n_cols):
        name = get_str(wide=True)
        (blen,) = get("<I")
        index[name] = (pos, blen)
        pos += blen
    return {"cols": _LazyFooterCols(data, codecs, index), "axes": axes}


def pack_columns(
    cols: dict[str, np.ndarray],
    axes: dict[str, AxisChunks] | None = None,
    col_axis: dict[str, str] | None = None,
    level: int = 3,
    codec: str = CODEC_ZSTD,
) -> bytes:
    """Serialize columns. Columns named in col_axis are chunked along the
    given axis' row groups; others are stored as a single chunk."""
    return b"".join(pack_columns_stream(cols, axes, col_axis, level, codec))


_DCTX_LOCAL = threading.local()  # per-thread zstd contexts (see _dctx)


@dataclass
class ColumnFetch:
    """One planned cold read (ColumnPack.plan_fetch): the state the
    fetch and decode phases share. The byte estimates feed the stream
    pipeline's admission budget BEFORE any IO happens."""

    pack: "ColumnPack"
    full: list  # (name, meta, dst start) full-column wants
    recs: list  # (chunk rec, dst_pos >= 0 | -1 for chunk-cache-only)
    cached: list  # (raw bytes, dst_pos, raw_len) chunk-cache hits
    runs: list  # coalesced (file off, end, members) ranged reads
    raw_bytes: int  # full-column decode output (dst buffer size)
    stored_bytes: int  # compressed bytes the fetch phase will read
    bufs: list | None = None  # fetch output (run buffers)
    src_pos: dict | None = None  # chunk file off -> offset in joined src

    @property
    def est_bytes(self) -> int:
        """Peak host RAM of running this plan: fetched compressed bytes
        + every decode destination."""
        sliced = sum(r[2] for r, d in self.recs if d < 0)
        return self.stored_bytes + self.raw_bytes + sliced


class ColumnPack:
    """Lazy chunked-column reader over a backend object via range reads."""

    # decompressed-chunk LRU budget, shared per pack: the host-RAM analog
    # of the OS page cache the reference's parquet reader leans on --
    # random trace materialization re-touches the same row-group chunks
    CHUNK_CACHE_BYTES = 256 << 20

    def __init__(self, read_range, total_size: int):
        """read_range(offset, length) -> bytes."""
        self._read_range = read_range
        self._size = total_size
        tail = self._read_range(total_size - _TAIL.size, _TAIL.size)
        flen, magic = _TAIL.unpack(tail)
        if magic != MAGIC:
            raise ValueError("not a vtpu column pack (bad magic)")
        fbytes = self._read_range(total_size - _TAIL.size - flen, flen)
        footer = (_decode_footer_binary(fbytes)
                  if fbytes[:4] == _BF_MARKER else json.loads(fbytes))
        self._cols: dict[str, dict] = footer["cols"]
        self.axes: dict[str, AxisChunks] = {
            k: AxisChunks(v) for k, v in footer.get("axes", {}).items()
        }
        self.bytes_read = _TAIL.size + flen  # inspected-bytes accounting
        self._io_lock = threading.Lock()  # bytes_read is read-modify-write
        self._cache: OrderedDict[int, bytes] = OrderedDict()  # chunk offset -> raw
        self._cache_bytes = 0
        self._cache_lock = threading.Lock()
        # assembled full-column LRU (name -> readonly ndarray): repeat
        # full-column readers (the host search engine, trace_index) skip
        # the per-chunk join + frombuffer copy entirely; chunks decode
        # straight into the final buffer (native batch) on first touch
        self._arrays: OrderedDict[str, np.ndarray] = OrderedDict()
        self._arrays_bytes = 0

    def _count_read(self, n: int) -> None:
        with self._io_lock:
            self.bytes_read += n

    def preload(self) -> None:
        """Fetch the WHOLE pack with one ranged read and serve later
        reads from memory. For small blocks (compaction inputs, the
        many-tiny-blocks shape) this replaces dozens of per-chunk
        backend reads/opens with one. Idempotent: the compaction
        pipeline's prefetch stage may run it before the merge stage
        calls it again; the second call must not re-copy the pack."""
        if getattr(self, "_preloaded", False):
            return
        data = self._read_range(0, self._size)
        self._count_read(len(data))
        self._read_range = lambda off, ln: data[off : off + ln]
        self._count_read = lambda n: None  # already counted in full
        self._preloaded = True

    @staticmethod
    def _dctx() -> "zstandard.ZstdDecompressor":
        """zstd contexts are NOT thread-safe: concurrent decompress on a
        shared context intermittently fails with "data corruption
        detected" (readers run in IO pools). One context per THREAD,
        shared across every pack (contexts are stateless between calls)."""
        d = getattr(_DCTX_LOCAL, "d", None)
        if d is None:
            d = _DCTX_LOCAL.d = zstandard.ZstdDecompressor()
        return d

    def _zstd_one(self, data: bytes, raw_len: int) -> bytes:
        """Decode ONE zstd chunk, native first: on wheel-less images the
        python fallback is the zlib shim, which can't read the real zstd
        frames the native compressor writes -- and vice versa, the
        native decoder refuses shim (zlib) bytes, so each side's output
        always finds its decoder."""
        from ..native import block_decompress_chunks

        outs = block_decompress_chunks("zstd", [data], [raw_len])
        if outs is not None:
            return outs[0]
        return self._dctx().decompress(data, max_output_size=raw_len)

    @classmethod
    def from_bytes(cls, data: bytes) -> "ColumnPack":
        return cls(lambda off, ln: data[off : off + ln], len(data))

    def names(self) -> list[str]:
        return list(self._cols)

    def has(self, name: str) -> bool:
        return name in self._cols

    def n_rows_of(self, name: str) -> int:
        """Row count of a column from footer metadata alone -- no chunk
        IO (pre-read budget estimates)."""
        meta = self._cols.get(name)
        return int(meta["shape"][0]) if meta else 0

    def dtype_of(self, name: str) -> np.dtype | None:
        """A column's dtype from footer metadata alone (None if the
        pack has no such column)."""
        meta = self._cols.get(name)
        return np.dtype(meta["dtype"]) if meta else None

    def _cache_get(self, off: int) -> bytes | None:
        with self._cache_lock:
            hit = self._cache.get(off)
            if hit is not None:
                self._cache.move_to_end(off)
            return hit

    def _cache_put(self, off: int, raw: bytes) -> None:
        if len(raw) > self.CHUNK_CACHE_BYTES // 4:
            return  # one huge chunk must not wipe the whole cache
        with self._cache_lock:
            if off in self._cache:
                return
            self._cache[off] = raw
            self._cache_bytes += len(raw)
            while self._cache_bytes > self.CHUNK_CACHE_BYTES and self._cache:
                _, old = self._cache.popitem(last=False)
                self._cache_bytes -= len(old)

    def _chunk(self, rec: list) -> bytes:
        off, stored_len, raw_len, codec = rec
        if raw_len == 0 and stored_len == 0:
            # zero-length chunks share the byte offset of the NEXT chunk
            # (writer advances offset by stored size) -- never cache them
            # under that offset or they poison the real chunk's entry
            return b""
        hit = self._cache_get(off)
        if hit is not None:
            return hit
        data = self._read_range(off, stored_len)
        self._count_read(stored_len)
        if codec == CODEC_ZSTD:
            data = self._zstd_one(data, raw_len)
        elif codec == CODEC_CONST:
            data = data * (raw_len // stored_len)  # tile the stored row
        elif codec != CODEC_RAW:
            data = _EXTRA_CODECS[codec][1](data, raw_len)  # codec matrix
        self._cache_put(off, data)
        return data

    def _chunks(self, recs: list[list]) -> bytes:
        """Fetch + decode many chunks; zstd chunks decompress as one
        threaded native batch when >1 (native/vtpu_native.cc)."""
        parts: list[bytes | None] = [
            b"" if (rec[1] == 0 and rec[2] == 0) else self._cache_get(rec[0])
            for rec in recs
        ]
        miss = [i for i, p in enumerate(parts) if p is None]
        zst = [i for i in miss if recs[i][3] == CODEC_ZSTD]
        if len(zst) > 1:
            from ..native import available, zstd_decompress_chunks

            if available():
                outs = zstd_decompress_chunks(
                    [self._read_range(recs[i][0], recs[i][1]) for i in zst],
                    [recs[i][2] for i in zst],
                )
                if outs is not None:
                    self._count_read(sum(recs[i][1] for i in zst))
                    for i, raw in zip(zst, outs):
                        parts[i] = raw
                        self._cache_put(recs[i][0], raw)
        for i in miss:
            if parts[i] is None:
                parts[i] = self._chunk(recs[i])
        return b"".join(parts)

    def chunk_codecs(self) -> set[str]:
        """Every chunk codec present in the pack -- footer metadata
        only, no IO (the compaction passthrough's codec-match gate)."""
        return {r[3] for meta in self._cols.values() for r in meta["chunks"]}

    def has_cached_array(self, name: str) -> bool:
        """True when a full-column read of `name` is a cache hit (used by
        the search engine's host-vs-device cost estimate)."""
        with self._cache_lock:
            return name in self._arrays

    def _arrays_get(self, name: str) -> np.ndarray | None:
        with self._cache_lock:
            hit = self._arrays.get(name)
            if hit is not None:
                self._arrays.move_to_end(name)
            return hit

    def _arrays_put(self, name: str, arr: np.ndarray) -> None:
        # shares the chunk cache's byte budget (the two caches together
        # are the pack's RAM footprint). Over-budget eviction drops
        # chunk bytes first (the array holds the same data assembled),
        # then other arrays LRU -- never the entry just inserted, so a
        # single large column always stays cached for its repeat readers
        if arr.nbytes > self.CHUNK_CACHE_BYTES:
            return
        with self._cache_lock:
            if name in self._arrays:
                return
            self._arrays[name] = arr
            self._arrays_bytes += arr.nbytes
            while (self._arrays_bytes + self._cache_bytes > self.CHUNK_CACHE_BYTES
                   and self._cache):
                _, old = self._cache.popitem(last=False)
                self._cache_bytes -= len(old)
            while (self._arrays_bytes + self._cache_bytes > self.CHUNK_CACHE_BYTES
                   and len(self._arrays) > 1):
                n, old = next(iter(self._arrays.items()))
                if n == name:
                    break
                del self._arrays[n]
                self._arrays_bytes -= old.nbytes

    def _read_column_into(self, meta: dict) -> np.ndarray | None:
        """Decode a whole column straight into its final buffer. A
        column's chunks sit ADJACENT in the pack, so every run of
        uncached zstd chunks is fetched with ONE ranged read and
        decompressed from that buffer in place -- no per-chunk bytes
        objects, no joins, no per-chunk file opens. None -> caller falls
        back to the chunk-join path."""
        from ..native import available, zstd_decompress_ranges

        if not available():
            return None
        recs = [r for r in meta["chunks"] if r[2] > 0]
        dst = np.empty(int(sum(r[2] for r in recs)), dtype=np.uint8)
        # classify chunks, then coalesce stored-adjacent zstd misses
        z_miss: list[tuple[int, int, int, int]] = []  # (off, stored, raw, dst_pos)
        other: list[tuple[list, int]] = []  # (rec, dst_pos)
        pos = 0
        for rec in recs:
            off, stored, raw_len, codec = rec
            hit = self._cache_get(off)
            if hit is not None:
                dst[pos : pos + raw_len] = np.frombuffer(hit, dtype=np.uint8)
            elif codec == CODEC_ZSTD:
                z_miss.append((off, stored, raw_len, pos))
            else:
                other.append((rec, pos))
            pos += raw_len
        counted = 0
        if z_miss:
            in_offs = np.empty(len(z_miss), np.int64)
            in_lens = np.empty(len(z_miss), np.int64)
            out_offs = np.empty(len(z_miss), np.int64)
            out_lens = np.empty(len(z_miss), np.int64)
            runs: list[tuple[int, int, int]] = []  # (file_off, length, first_idx)
            for i, (off, stored, raw_len, dpos) in enumerate(z_miss):
                in_lens[i] = stored
                out_offs[i] = dpos
                out_lens[i] = raw_len
                if runs and runs[-1][0] + runs[-1][1] == off:
                    fo, ln, fi = runs[-1]
                    runs[-1] = (fo, ln + stored, fi)
                else:
                    runs.append((off, stored, i))
                in_offs[i] = off - runs[-1][0]  # provisional, rebased below
            bufs = []
            base = 0
            for fo, ln, fi in runs:
                bufs.append(self._read_range(fo, ln))
                counted += ln
                # rebase this run's chunk offsets to the joined buffer
                hi = fi
                while hi < len(z_miss) and z_miss[hi][0] >= fo and z_miss[hi][0] < fo + ln:
                    in_offs[hi] = base + (z_miss[hi][0] - fo)
                    hi += 1
                base += ln
            src = (np.frombuffer(bufs[0], dtype=np.uint8) if len(bufs) == 1
                   else np.frombuffer(b"".join(bufs), dtype=np.uint8))
            if not zstd_decompress_ranges(src, in_offs, in_lens, dst, out_offs, out_lens):
                # the ranged reads above really happened: account them
                # before falling back (the fallback counts only its own)
                self._count_read(counted)
                return None
        for (off, stored, raw_len, codec), dpos in other:
            data = self._read_range(off, stored)
            counted += stored
            if codec == CODEC_CONST:
                # tile the one stored row across the chunk, in place
                dst[dpos : dpos + raw_len].reshape(-1, stored)[:] = (
                    np.frombuffer(data, dtype=np.uint8))
                continue
            if codec != CODEC_RAW:
                data = _EXTRA_CODECS[codec][1](data, raw_len)
            dst[dpos : dpos + raw_len] = np.frombuffer(data, dtype=np.uint8)
        self._count_read(counted)
        out = dst.view(np.dtype(meta["dtype"])).reshape(meta["shape"])
        out.flags.writeable = False  # cached entries are shared across readers
        return out

    def read(self, name: str) -> np.ndarray:
        meta = self._cols[name]
        hit = self._arrays_get(name)
        if hit is not None:
            return hit
        arr = self._read_column_into(meta)
        if arr is None:
            # fallback already populated the CHUNK cache (old behavior);
            # caching the assembled array too would charge the same bytes
            # to the shared budget twice
            raw = self._chunks(meta["chunks"])
            return np.frombuffer(raw, dtype=np.dtype(meta["dtype"])).reshape(meta["shape"])
        self._arrays_put(name, arr)
        return arr

    def read_groups(self, name: str, groups: list[int]) -> np.ndarray:
        """Concatenated rows of the given row groups (in the given order).
        Column must be axis-chunked."""
        meta = self._cols[name]
        if meta["axis"] is None:
            raise ValueError(f"column {name} is not axis-chunked")
        full = self._arrays_get(name)
        if full is not None:
            # a full-column read already paid for these rows: slice the
            # cached array instead of re-fetching chunks from the backend
            offs = self.axes[meta["axis"]].offsets
            parts = [full[offs[g] : offs[g + 1]] for g in groups]
            return parts[0] if len(parts) == 1 else np.concatenate(parts)
        raw = self._chunks([meta["chunks"][g] for g in groups])
        shape = [-1] + meta["shape"][1:]
        return np.frombuffer(raw, dtype=np.dtype(meta["dtype"])).reshape(shape)

    def read_many(self, names: list[str]) -> dict[str, np.ndarray]:
        # read() decodes each full column natively into its final buffer
        # (and caches the array), so no chunk-level warm pass is needed
        return {n: self.read(n) for n in names if n in self._cols}

    def read_groups_many(
        self, wants: list[tuple[str, list[int] | None]]
    ) -> dict[str, np.ndarray]:
        """Batched multi-column read: (name, groups|None for all). ALL
        columns' missing chunks decompress as ONE native threaded batch,
        so a trace materialization that touches 20 columns pays one
        parallel decode instead of 20 serial ones."""
        wants = [(n, g) for n, g in wants if n in self._cols]
        # full-column wants decode natively inside read(); only the
        # row-group-sliced wants benefit from the chunk-level warm batch
        self.warm([(n, g) for n, g in wants if g is not None])
        out: dict[str, np.ndarray] = {}
        for name, groups in wants:
            out[name] = self.read(name) if groups is None else self.read_groups(name, groups)
        return out

    def warm(self, wants: list[tuple[str, list[int] | None]]) -> None:
        """Prefetch + batch-decompress every missing chunk of the wanted
        (column, groups) set (full columns land in the array cache,
        group slices in the chunk cache)."""
        self._run_plan(self.plan_fetch(wants))

    def warm_columns(self, names: list[str], gap_bytes: int = 256 << 10) -> None:
        """Cold-read accelerator: fetch EVERY missing chunk of the named
        columns with a few coalesced ranged reads (runs split only at
        gaps > gap_bytes, so interleaved unwanted columns aren't pulled
        wholesale), decompress ALL of them in one batch (threaded native
        when available) straight into one destination buffer, and cache
        the assembled per-column arrays. A cold query touching 12 small
        columns pays ~2 fixed IO costs instead of 12."""
        self._run_plan(self.plan_fetch([(n, None) for n in names],
                                       gap_bytes=gap_bytes))

    def _run_plan(self, cf: "ColumnFetch | None") -> None:
        """Run a fetch plan inline with the pipeline's per-stage
        kerneltel timings -- the serial (no-overlap) form of the stream
        stages, so EVERY cold ranged read shows up under
        tempo_stream_stage_seconds whichever path issued it. The window
        records as its own run: inline stage-seconds then contribute
        matching wall-seconds, so overlap_ratio stays ~1 (honestly
        sequential) for workloads that never pipeline, instead of
        inflating the numerator against someone else's wall."""
        if cf is None:
            return
        import time as _time

        from ..util.kerneltel import TEL

        t_run = _time.perf_counter()
        with TEL.stage("stream:fetch"):
            self.fetch_ranges(cf)
        with TEL.stage("stream:decompress"):
            self.decode_fetched(cf)
        TEL.record_stream_run(_time.perf_counter() - t_run)

    # ------------------------------------------------- staged cold reads
    # The cold-read pipeline's unit of work: plan (footer metadata only)
    # -> fetch (the ranged IO) -> decode (decompress + assemble). The
    # streaming pipeline (ops/stream.py) runs the phases of DIFFERENT
    # blocks concurrently -- block N decodes while block N+1's ranged
    # reads are in flight; warm/warm_columns run them back to back.

    def plan_fetch(self, wants: list[tuple[str, list[int] | None]],
                   gap_bytes: int = 256 << 10) -> "ColumnFetch | None":
        """Build the fetch/decode plan for (column, groups|None) wants
        from footer metadata + cache state alone -- no IO. None when
        every want is already cached (nothing to do)."""
        full: list[tuple[str, dict, int]] = []  # (name, meta, dst start)
        recs: list[tuple[list, int]] = []  # (chunk rec, dst_pos; -1 = cache-only)
        cached: list[tuple[bytes, int, int]] = []  # dst copies of cache hits
        pos = 0
        seen: set[str] = set()
        for name, groups in wants:
            meta = self._cols.get(name)
            if meta is None or self.has_cached_array(name):
                continue  # read/read_groups serve it from the array cache
            if groups is None:
                if name in seen:
                    continue  # dedupe; call sites overlap
                seen.add(name)
                pos = (pos + 15) & ~15  # dtype-aligned column starts
                full.append((name, meta, pos))
                for r in meta["chunks"]:
                    if r[2] <= 0:
                        continue
                    hit = self._cache_get(r[0])
                    if hit is not None:
                        # already decoded (e.g. a prior find-by-id's
                        # read_groups): copy into dst, no refetch
                        cached.append((hit, pos, r[2]))
                    else:
                        recs.append((r, pos))
                    pos += r[2]
            else:
                chunks = meta["chunks"]
                for g in groups:
                    r = chunks[g]
                    if r[2] > 0 and self._cache_get(r[0]) is None:
                        recs.append((r, -1))
        if not full and not recs:
            return None
        # coalesce missing chunks into gap-bounded file runs
        by_off = sorted(recs, key=lambda t: t[0][0])
        runs: list[tuple[int, int, list]] = []  # (off, end, members)
        for r, dpos in by_off:
            if runs and r[0] - runs[-1][1] <= gap_bytes and r[0] >= runs[-1][0]:
                off, end, members = runs[-1]
                runs[-1] = (off, max(end, r[0] + r[1]), members + [(r, dpos)])
            else:
                runs.append((r[0], r[0] + r[1], [(r, dpos)]))
        return ColumnFetch(self, full, recs, cached, runs, pos,
                           sum(r[1] for r, _ in recs))

    def fetch_ranges(self, cf: "ColumnFetch") -> None:
        """The IO phase: issue the plan's coalesced ranged reads.
        Idempotent; counts inspected bytes as it reads."""
        if cf.bufs is not None:
            return
        src_parts: list[bytes] = []
        src_pos: dict[int, int] = {}  # chunk file off -> offset in joined src
        base = 0
        counted = 0
        for off, end, members in cf.runs:
            data = self._read_range(off, end - off)
            src_parts.append(data)
            counted += sum(m[0][1] for m in members)
            for r, _ in members:
                src_pos[r[0]] = base + (r[0] - off)
            base += len(data)
        self._count_read(counted)
        cf.bufs = src_parts
        cf.src_pos = src_pos

    def decode_fetched(self, cf: "ColumnFetch") -> None:
        """The decode phase: decompress every fetched chunk (native
        threaded batch per codec when available, per-chunk Python
        otherwise), assemble full-column wants into the array cache and
        sliced wants into the chunk cache."""
        if cf.bufs is None:
            raise ValueError("decode_fetched before fetch_ranges")
        src_pos = cf.src_pos or {}
        src = (np.frombuffer(cf.bufs[0], np.uint8) if len(cf.bufs) == 1
               else np.frombuffer(b"".join(cf.bufs), np.uint8)
               ) if cf.bufs else np.empty(0, np.uint8)
        dst = np.empty(cf.raw_bytes, np.uint8)
        for raw, dpos, raw_len in cf.cached:
            dst[dpos : dpos + raw_len] = np.frombuffer(raw, np.uint8)
        # full-column chunks decode straight into dst; sliced (cache-only)
        # chunks decode into a scratch tail appended after dst's columns
        into_dst = [(r, d) for r, d in cf.recs if d >= 0]
        sliced = [r for r, d in cf.recs if d < 0]
        scratch = np.empty(sum(r[2] for r in sliced), np.uint8)
        placed: list[tuple[list, np.ndarray, int]] = []  # (rec, buf, pos)
        spos = 0
        for r in sliced:
            placed.append((r, scratch, spos))
            spos += r[2]
        for r, d in into_dst:
            placed.append((r, dst, d))
        # batch the native-range codecs per codec group; everything else
        # (const/raw/gzip/lzma, or native refusal) decodes per chunk
        from ..native import block_decompress_ranges

        leftovers: list[tuple[list, np.ndarray, int]] = []
        by_codec: dict[str, list[tuple[list, np.ndarray, int]]] = {}
        for item in placed:
            codec = item[0][3]
            if codec in _NATIVE_RANGE_CODECS:
                by_codec.setdefault(codec, []).append(item)
            else:
                leftovers.append(item)
        for codec, items in by_codec.items():
            # dst and scratch are distinct buffers: one ranges call per
            # (codec, destination) pair
            for buf in (dst, scratch):
                part = [(r, p) for r, b, p in items if b is buf]
                if not part:
                    continue
                ok = block_decompress_ranges(
                    codec, src,
                    np.asarray([src_pos[r[0]] for r, _ in part], np.int64),
                    np.asarray([r[1] for r, _ in part], np.int64),
                    buf,
                    np.asarray([p for _, p in part], np.int64),
                    np.asarray([r[2] for r, _ in part], np.int64),
                )
                if not ok:
                    leftovers.extend((r, buf, p) for r, p in part)
        for r, buf, p in leftovers:
            chunk = src[src_pos[r[0]] : src_pos[r[0]] + r[1]]
            if r[3] == CODEC_CONST:
                buf[p : p + r[2]].reshape(-1, r[1])[:] = chunk
            elif r[3] == CODEC_RAW:
                buf[p : p + r[2]] = chunk
            elif r[3] == CODEC_ZSTD:
                dec = self._zstd_one(chunk.tobytes(), r[2])
                buf[p : p + r[2]] = np.frombuffer(dec, np.uint8)
            else:
                dec = _EXTRA_CODECS[r[3]][1](chunk.tobytes(), r[2])
                buf[p : p + r[2]] = np.frombuffer(dec, np.uint8)
        # sliced chunks land in the chunk cache for read_groups
        for r, buf, p in placed:
            if buf is scratch:
                self._cache_put(r[0], buf[p : p + r[2]].tobytes())
        # COPY each column out of the shared buffer: cached views over
        # one big base would pin the whole buffer for as long as any one
        # entry lives, making LRU eviction free nothing (the copy is a
        # fraction of the decompress cost just paid)
        for name, meta, start in cf.full:
            n_bytes = sum(r[2] for r in meta["chunks"] if r[2] > 0)
            out = dst[start : start + n_bytes].copy().view(np.dtype(meta["dtype"]))
            out = out.reshape(meta["shape"])
            out.flags.writeable = False
            self._arrays_put(name, out)
        cf.bufs = None  # free the fetched bytes; decode is one-shot

    def column_stats(self) -> list[dict]:
        """Per-column layout summary (name, dtype, rows, chunks, stored/
        raw bytes, codecs) -- defined beside the footer format so layout
        knowledge never leaks to callers."""
        out = []
        for name, meta in self._cols.items():
            out.append({
                "name": name,
                "dtype": meta["dtype"],
                "rows": meta["shape"][0],
                "chunks": len(meta["chunks"]),
                "stored": sum(rec[1] for rec in meta["chunks"]),
                "raw": sum(rec[2] for rec in meta["chunks"]),
                "codecs": sorted({rec[3] for rec in meta["chunks"]}),
            })
        return out

    def _broadcast_const_cols(self) -> dict[str, np.ndarray]:
        """Columns whose every chunk is const with one identical row,
        as stride-0 broadcast views (zero decode, zero memory)."""
        out: dict[str, np.ndarray] = {}
        for name, meta in self._cols.items():
            chs = [c for c in meta["chunks"] if c[2] > 0]
            if not chs or any(c[3] != CODEC_CONST for c in chs):
                continue
            rows = {self._read_range(c[0], c[1]) for c in chs}  # tiny reads
            self._count_read(sum(c[1] for c in chs))
            if len(rows) != 1:
                continue
            dt = np.dtype(meta["dtype"])
            shape = tuple(meta["shape"])
            rv = np.frombuffer(next(iter(rows)), dtype=dt).reshape(shape[1:])
            out[name] = np.broadcast_to(rv, shape)
        return out

    def read_all(self, broadcast_const: bool = False,
                 independent: bool = False) -> dict[str, np.ndarray]:
        """Every column, zero-copy: ONE destination buffer laid out
        column-after-column, every zstd chunk decompressed straight into
        its final position (native batch), raw chunks memcpy'd, then each
        column is a frombuffer VIEW of the buffer. The bulk-read path
        compaction uses -- no chunk cache round trips, no joins.

        broadcast_const=True returns fully-constant columns as stride-0
        np.broadcast_to views instead of materialized tiles (the
        compaction merge's const fast path); such views are read-only
        and NOT contiguous -- callers that hand pointers to native code
        must np.ascontiguousarray first.

        independent=True copies each column out of the shared buffer
        (one extra memcpy pass) so a caller can FREE columns one by one
        -- views over one base would pin the whole buffer for as long
        as any single column lives (the compaction merge's
        consume-as-you-go path)."""
        from ..native import available, zstd_decompress_into

        bc = self._broadcast_const_cols() if broadcast_const else {}

        def _fallback():
            # honor independent on the fallback paths too: read() hands
            # back arrays pinned in the pack's LRU cache, which would
            # silently void the caller's free-one-by-one contract
            self.warm([(n, None) for n in self._cols if n not in bc])
            return {
                n: bc[n] if n in bc
                else (self.read(n).copy() if independent else self.read(n))
                for n in self._cols
            }

        if not available():
            return _fallback()

        col_base: dict[str, int] = {}
        z_chunks: list[bytes] = []
        z_offs: list[int] = []
        z_lens: list[int] = []
        raw_parts: list[tuple[int, bytes]] = []
        const_parts: list[tuple[int, bytes, int]] = []  # (pos, row, raw_len)
        counted = 0  # this attempt's IO accounting, for relative rollback
        pos = 0
        for name, meta in self._cols.items():
            if name in bc:
                continue
            pos = (pos + 15) & ~15  # keep every column view 16B-aligned
            col_base[name] = pos
            for off, stored, raw_len, codec in meta["chunks"]:
                if raw_len == 0:
                    continue
                data = self._read_range(off, stored)
                self._count_read(stored)
                counted += stored
                if codec == CODEC_ZSTD:
                    z_chunks.append(data)
                    z_offs.append(pos)
                    z_lens.append(raw_len)
                elif codec == CODEC_CONST:
                    const_parts.append((pos, data, raw_len))
                else:
                    if codec != CODEC_RAW:
                        data = _EXTRA_CODECS[codec][1](data, raw_len)
                    raw_parts.append((pos, data))
                pos += raw_len
        dst = np.empty(pos, dtype=np.uint8)
        if z_chunks and not zstd_decompress_into(
            z_chunks, dst, np.asarray(z_offs), np.asarray(z_lens)
        ):
            # native refused mid-flight: fall back wholesale (and undo
            # this attempt's IO accounting -- the fallback re-counts).
            # Relative subtraction under the lock: a plain reset would
            # clobber concurrent readers' increments.
            self._count_read(-counted)
            return _fallback()
        for p, data in raw_parts:
            dst[p : p + len(data)] = np.frombuffer(data, dtype=np.uint8)
        for p, row, raw_len in const_parts:
            dst[p : p + raw_len].reshape(-1, len(row))[:] = np.frombuffer(
                row, dtype=np.uint8)
        out: dict[str, np.ndarray] = {}
        for name, meta in self._cols.items():
            if name in bc:
                out[name] = bc[name]
                continue
            dt = np.dtype(meta["dtype"])
            n_bytes = int(np.prod(meta["shape"], dtype=np.int64)) * dt.itemsize
            base = col_base[name]
            col = dst[base : base + n_bytes]
            if independent:
                col = col.copy()
            out[name] = col.view(dt).reshape(meta["shape"])
        return out
