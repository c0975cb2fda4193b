// Native runtime layer: the host-side hot loops around the TPU compute
// path (SURVEY.md 2.10 "native components"): batch hashing for ring
// tokens + bloom positions, bloom filter insertion, WAL record framing,
// and multi-threaded zstd (de)compression feeding column chunks.
//
// The reference leans on optimized Go libraries for these (klauspost
// compression, willf/bloom, segmentio/parquet-go page codecs); here the
// equivalents are C++ behind a C ABI consumed through ctypes
// (tempo_tpu/native/__init__.py), with pure-Python fallbacks when the
// shared library is absent.
//
// Build: make -C native   (g++ -O3 -shared -fPIC, links libzstd)

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

// images with the runtime libzstd but no dev headers (dpkg ships
// libzstd1 without libzstd-dev) still build: the handful of stable-ABI
// symbols used below are declared directly and the Makefile links the
// soname file (-l:libzstd.so.1) when the dev symlink is absent
#if defined(__has_include) && !__has_include(<zstd.h>)
extern "C" {
typedef struct ZSTD_CCtx_s ZSTD_CCtx;
typedef struct ZSTD_DCtx_s ZSTD_DCtx;
static const int ZSTD_c_compressionLevel = 100;
size_t ZSTD_compressBound(size_t srcSize);
unsigned ZSTD_isError(size_t code);
ZSTD_CCtx* ZSTD_createCCtx(void);
size_t ZSTD_freeCCtx(ZSTD_CCtx* cctx);
size_t ZSTD_CCtx_setParameter(ZSTD_CCtx* cctx, int param, int value);
size_t ZSTD_compress2(ZSTD_CCtx* cctx, void* dst, size_t dstCapacity,
                      const void* src, size_t srcSize);
ZSTD_DCtx* ZSTD_createDCtx(void);
size_t ZSTD_freeDCtx(ZSTD_DCtx* dctx);
size_t ZSTD_decompressDCtx(ZSTD_DCtx* dctx, void* dst, size_t dstCapacity,
                           const void* src, size_t srcSize);
}
#else
#include <zstd.h>
#endif

extern "C" {

// ---------------------------------------------------------------- hashing

// fnv1a32 over (tenant || trace_id) per row: ring tokens for a batch of
// trace ids (pkg/util/hash.go TokenFor analog).
void vtpu_ring_tokens(const uint8_t* tenant, int tenant_len,
                      const uint8_t* ids, int id_len, int n,
                      uint32_t* out) {
  for (int i = 0; i < n; i++) {
    uint32_t h = 2166136261u;
    for (int j = 0; j < tenant_len; j++) {
      h ^= tenant[j];
      h *= 16777619u;
    }
    const uint8_t* id = ids + (size_t)i * id_len;
    for (int j = 0; j < id_len; j++) {
      h ^= id[j];
      h *= 16777619u;
    }
    out[i] = h;
  }
}

// splitmix64: the bloom position generator (util/hashing.py bloom_hashes)
static inline uint64_t splitmix64(uint64_t x) {
  x += 0x9E3779B97F4A7C15ull;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ull;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBull;
  return x ^ (x >> 31);
}

static inline uint64_t fnv1a64(const uint8_t* p, int n) {
  uint64_t h = 14695981039346656037ull;
  for (int i = 0; i < n; i++) {
    h ^= p[i];
    h *= 1099511628211ull;
  }
  return h;
}

// ------------------------------------------------------------------ bloom

static inline uint32_t fnv1a32(const uint8_t* p, int n) {
  uint32_t h = 2166136261u;
  for (int i = 0; i < n; i++) {
    h ^= p[i];
    h *= 16777619u;
  }
  return h;
}

// Batch-insert n trace ids into a sharded bloom filter. Bit-for-bit the
// same scheme as the Python side (block/bloom.py + util/hashing.py):
// shard = fnv1a32(id) % n_shards; Kirsch-Mitzenmacher double hashing
// h_i = h1 + i*(splitmix64(h1)|1) over fnv1a64(id).
void vtpu_bloom_add_batch(uint32_t* words, int n_shards, int words_per_shard,
                          int shard_bits, int k,
                          const uint8_t* ids, int id_len, int n) {
  for (int i = 0; i < n; i++) {
    const uint8_t* id = ids + (size_t)i * id_len;
    int shard = (int)(fnv1a32(id, id_len) % (uint32_t)n_shards);
    uint32_t* w = words + (size_t)shard * words_per_shard;
    uint64_t h1 = fnv1a64(id, id_len);
    uint64_t h2 = splitmix64(h1) | 1ull;
    for (int j = 0; j < k; j++) {
      uint32_t pos = (uint32_t)((h1 + (uint64_t)j * h2) % (uint64_t)shard_bits);
      w[pos >> 5] |= (1u << (pos & 31));
    }
  }
}

// ------------------------------------------------------------- wal frames

// Scan uvarint-framed records: data = repeated [uvarint len][body].
// Fills offsets/lengths (body position/size); returns count, or -count-1
// if a torn tail starts at offsets[count] (replay truncates there).
int vtpu_varint_frames(const uint8_t* data, int64_t n,
                       int64_t* offsets, int64_t* lengths, int max_frames) {
  int64_t pos = 0;
  int count = 0;
  while (pos < n && count < max_frames) {
    int64_t start = pos;
    uint64_t len = 0;
    int shift = 0;
    bool ok = false;
    while (pos < n && shift < 64) {
      uint8_t b = data[pos++];
      len |= (uint64_t)(b & 0x7F) << shift;
      if (!(b & 0x80)) {
        ok = true;
        break;
      }
      shift += 7;
    }
    if (!ok || len > (uint64_t)(n - pos)) {  // unsigned: >=2^63 len must read as torn, not negative
      offsets[count] = start;  // torn tail marker
      return -count - 1;
    }
    offsets[count] = pos;
    lengths[count] = (int64_t)len;
    pos += (int64_t)len;
    count++;
  }
  return count;
}

// ------------------------------------------------------------ id bisect

// Batched binary search of q 16-byte trace ids over a sorted (n, 16)
// id table (memcmp order == big-endian lexicographic == the block's
// trace.id sort). out[i] = row of an exact match, else -1. The host
// twin of the device lockstep-bisection kernel (ops/find.py): numpy's
// void16 searchsorted pays per-probe object machinery; this is a tight
// memcmp loop.
void vtpu_lex_bisect16(const uint8_t* ids, int64_t n, const uint8_t* queries,
                       int64_t q, int32_t* out) {
  for (int64_t i = 0; i < q; i++) {
    const uint8_t* key = queries + i * 16;
    int64_t lo = 0, hi = n;
    while (lo < hi) {
      int64_t mid = (lo + hi) >> 1;
      if (memcmp(ids + mid * 16, key, 16) < 0) lo = mid + 1;
      else hi = mid;
    }
    out[i] = (lo < n && memcmp(ids + lo * 16, key, 16) == 0)
                 ? (int32_t)lo : -1;
  }
}

// --------------------------------------------------------- otlp span scan

// Structural scan of an OTLP ExportTraceServiceRequest / TracesData:
// locate every span submessage (byte range + owning resource/scope
// envelope) and pull exactly three fields out of each span body --
// trace_id (1), start (7) and end (8) -- WITHOUT decoding anything
// else. The distributor's fast ingest path re-batches spans by trace
// id by SPLICING these ranges back together under re-used envelope
// bytes (wire/otlp_splice.py), replacing the Python
// decode-model-re-encode round trip.
//
// Envelopes: for each ResourceSpans, every field EXCEPT scope_spans(2)
// verbatim (tag+len+body); for each ScopeSpans, every field except
// spans(2). Copied into env_buf so the Python side splices with two
// slices per group.
//
// Returns 0 ok; 1 malformed (caller falls back to the Python decode
// path); 2 capacity exceeded (caller re-calls with larger buffers).

static inline bool oscan_varint(const uint8_t* d, int64_t n, int64_t* pos,
                                uint64_t* out) {
  uint64_t v = 0;
  int shift = 0;
  while (*pos < n && shift < 64) {
    uint8_t b = d[(*pos)++];
    v |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *out = v;
      return true;
    }
    shift += 7;
  }
  return false;
}

int vtpu_otlp_scan(const uint8_t* buf, int64_t n,
                   int64_t* span_off, int64_t* span_len, int32_t* span_rs,
                   int32_t* span_ss, uint8_t* trace_ids, uint64_t* start_ns,
                   uint64_t* end_ns, int64_t cap_spans,
                   uint8_t* env_buf, int64_t cap_env,        // rs envelopes
                   uint8_t* senv_buf, int64_t cap_senv,      // ss envelopes
                   int64_t* rs_env_off, int64_t* rs_env_len, int64_t cap_rs,
                   int64_t* ss_env_off, int64_t* ss_env_len, int32_t* ss_rs,
                   int64_t cap_ss,
                   int64_t* counts /* [n_spans, n_rs, n_ss, env, senv] */) {
  int64_t ns_count = 0, nrs = 0, nss = 0, env_pos = 0, senv_pos = 0;
  int64_t pos = 0;
  while (pos < n) {  // TracesData: repeated resource_spans = 1
    uint64_t tag;
    int64_t tag_start = pos;
    (void)tag_start;
    if (!oscan_varint(buf, n, &pos, &tag)) return 1;
    uint64_t fno = tag >> 3, wt = tag & 7;
    if (wt != 2) return 1;  // top level is only length-delimited RS
    uint64_t len;
    // Compare unsigned against remaining bytes: casting len to int64_t
    // would let a crafted >=2^63 varint go negative and bypass the check.
    if (!oscan_varint(buf, n, &pos, &len) || len > (uint64_t)(n - pos)) return 1;
    if (fno != 1) {  // unknown top-level field: keep nothing, skip
      pos += (int64_t)len;
      continue;
    }
    // ---- one ResourceSpans
    if (nrs >= cap_rs) return 2;
    int64_t rs_idx = nrs++;
    rs_env_off[rs_idx] = env_pos;
    int64_t rs_end = pos + (int64_t)len;
    while (pos < rs_end) {
      int64_t f_start = pos;
      uint64_t ftag;
      if (!oscan_varint(buf, rs_end, &pos, &ftag)) return 1;
      uint64_t ffno = ftag >> 3, fwt = ftag & 7;
      int64_t body_off = pos, body_len = 0;
      if (fwt == 2) {
        uint64_t blen;
        if (!oscan_varint(buf, rs_end, &pos, &blen) ||
            blen > (uint64_t)(rs_end - pos))
          return 1;
        body_off = pos;
        body_len = (int64_t)blen;
        pos += body_len;
      } else if (fwt == 0) {
        uint64_t v;
        if (!oscan_varint(buf, rs_end, &pos, &v)) return 1;
      } else if (fwt == 1) {
        if (pos + 8 > rs_end) return 1;
        pos += 8;
      } else if (fwt == 5) {
        if (pos + 4 > rs_end) return 1;
        pos += 4;
      } else {
        return 1;
      }
      if (!(ffno == 2 && fwt == 2)) {  // non-scope_spans: envelope verbatim
        int64_t flen = pos - f_start;
        if (env_pos + flen > cap_env) return 2;
        memcpy(env_buf + env_pos, buf + f_start, (size_t)flen);
        env_pos += flen;
        continue;
      }
      // ---- one ScopeSpans
      if (nss >= cap_ss) return 2;
      int64_t ss_idx = nss++;
      ss_rs[ss_idx] = (int32_t)rs_idx;
      ss_env_off[ss_idx] = senv_pos;
      int64_t ss_end = body_off + body_len;
      int64_t spos = body_off;
      while (spos < ss_end) {
        int64_t sf_start = spos;
        uint64_t stag;
        if (!oscan_varint(buf, ss_end, &spos, &stag)) return 1;
        uint64_t sfno = stag >> 3, swt = stag & 7;
        int64_t sb_off = spos, sb_len = 0;
        if (swt == 2) {
          uint64_t blen;
          if (!oscan_varint(buf, ss_end, &spos, &blen) ||
              blen > (uint64_t)(ss_end - spos))
            return 1;
          sb_off = spos;
          sb_len = (int64_t)blen;
          spos += sb_len;
        } else if (swt == 0) {
          uint64_t v;
          if (!oscan_varint(buf, ss_end, &spos, &v)) return 1;
        } else if (swt == 1) {
          if (spos + 8 > ss_end) return 1;
          spos += 8;
        } else if (swt == 5) {
          if (spos + 4 > ss_end) return 1;
          spos += 4;
        } else {
          return 1;
        }
        if (!(sfno == 2 && swt == 2)) {  // non-span field: ss envelope
          int64_t flen = spos - sf_start;
          if (senv_pos + flen > cap_senv) return 2;
          memcpy(senv_buf + senv_pos, buf + sf_start, (size_t)flen);
          senv_pos += flen;
          continue;
        }
        // ---- one Span: record range + pull trace_id/start/end
        if (ns_count >= cap_spans) return 2;
        int64_t sp = ns_count++;
        span_off[sp] = sb_off;
        span_len[sp] = sb_len;
        span_rs[sp] = (int32_t)rs_idx;
        span_ss[sp] = (int32_t)ss_idx;
        start_ns[sp] = 0;
        end_ns[sp] = 0;
        bool got_tid = false;
        int64_t p2 = sb_off, sp_end = sb_off + sb_len;
        while (p2 < sp_end) {
          uint64_t t2;
          if (!oscan_varint(buf, sp_end, &p2, &t2)) return 1;
          uint64_t f2 = t2 >> 3, w2 = t2 & 7;
          if (w2 == 2) {
            uint64_t blen;
            if (!oscan_varint(buf, sp_end, &p2, &blen) ||
                blen > (uint64_t)(sp_end - p2))
              return 1;
            if (f2 == 1 && blen == 16) {
              memcpy(trace_ids + sp * 16, buf + p2, 16);
              got_tid = true;
            }
            p2 += (int64_t)blen;
          } else if (w2 == 1) {
            if (p2 + 8 > sp_end) return 1;
            uint64_t v;
            memcpy(&v, buf + p2, 8);  // little-endian hosts only (x86/arm)
            if (f2 == 7) start_ns[sp] = v;
            else if (f2 == 8) end_ns[sp] = v;
            p2 += 8;
          } else if (w2 == 0) {
            uint64_t v;
            if (!oscan_varint(buf, sp_end, &p2, &v)) return 1;
            // tolerate nonconformant varint timestamps
            if (f2 == 7) start_ns[sp] = v;
            else if (f2 == 8) end_ns[sp] = v;
          } else if (w2 == 5) {
            if (p2 + 4 > sp_end) return 1;
            p2 += 4;
          } else {
            return 1;
          }
        }
        if (!got_tid) return 1;  // spans without a 16B trace id: fall back
      }
      ss_env_len[ss_idx] = senv_pos - ss_env_off[ss_idx];
    }
    rs_env_len[rs_idx] = env_pos - rs_env_off[rs_idx];
  }
  counts[0] = ns_count;
  counts[1] = nrs;
  counts[2] = nss;
  counts[3] = env_pos;
  counts[4] = senv_pos;
  return 0;
}

// ----------------------------------------------------------- otlp splice

// Scan + group-by-trace-id + emit, one call: the distributor's whole
// rebatch loop (wire/otlp_splice.py used to drive vtpu_otlp_scan from
// Python and splice per-trace bytes in a Python loop -- the single
// biggest ingest cost). Emits finished wire segments back to back into
// `out`: 9-byte header (version 0x01, u32 start_s, u32 end_s, little
// endian -- wire/segment._HDR) followed by the per-trace TracesData
// built from envelope + span slices of the original payload.
//
// Returns 0 ok (counts = [n_traces, out_bytes, n_spans]);
//         1 malformed (caller falls back to the Python model path);
//         2 capacity: counts[0]/counts[1] carry the needed trace count
//           and out bytes -- re-call with buffers at least that big.

static inline int vsize(uint64_t v) {
  int s = 1;
  while (v >= 128) { v >>= 7; s++; }
  return s;
}

static inline void vput(uint8_t** p, uint64_t v) {
  while (v >= 128) { *(*p)++ = (uint8_t)(v | 0x80); v >>= 7; }
  *(*p)++ = (uint8_t)v;
}

int vtpu_otlp_splice(const uint8_t* buf, int64_t n,
                     uint8_t* out, int64_t cap_out,
                     uint8_t* tids_out, int64_t cap_traces,
                     int64_t* seg_off, int64_t* seg_len,
                     int64_t* start_s_out, int64_t* end_s_out,
                     int64_t* counts) {
  // scan with internally managed buffers (grow-on-demand mirrors the
  // Python binding's retry loop)
  std::vector<int64_t> sp_off, sp_len, rs_eoff, rs_elen, ss_eoff, ss_elen;
  std::vector<int32_t> sp_rs, sp_ss, ss_rsv;
  std::vector<uint8_t> tids, env, senv;
  std::vector<uint64_t> st_ns, en_ns;
  int64_t cap_spans = n / 24 + 16, cap_g = n / 64 + 8;
  int64_t c[5];
  int rc = 1;
  for (int t = 0; t < 4; t++) {
    sp_off.resize(cap_spans); sp_len.resize(cap_spans);
    sp_rs.resize(cap_spans); sp_ss.resize(cap_spans);
    tids.resize((size_t)cap_spans * 16);
    st_ns.resize(cap_spans); en_ns.resize(cap_spans);
    env.resize(n + 16); senv.resize(n + 16);
    rs_eoff.resize(cap_g); rs_elen.resize(cap_g);
    ss_eoff.resize(cap_g); ss_elen.resize(cap_g); ss_rsv.resize(cap_g);
    rc = vtpu_otlp_scan(buf, n, sp_off.data(), sp_len.data(), sp_rs.data(),
                        sp_ss.data(), tids.data(), st_ns.data(), en_ns.data(),
                        cap_spans, env.data(), (int64_t)env.size(),
                        senv.data(), (int64_t)senv.size(),
                        rs_eoff.data(), rs_elen.data(), cap_g,
                        ss_eoff.data(), ss_elen.data(), ss_rsv.data(), cap_g, c);
    if (rc == 2) { cap_spans *= 4; cap_g *= 4; continue; }
    break;
  }
  if (rc != 0) return 1;
  const int64_t k = c[0];
  counts[2] = k;
  if (k == 0) { counts[0] = 0; counts[1] = 0; return 0; }

  // stable order by 16-byte id keeps spans of a trace in payload order
  std::vector<int32_t> order(k);
  for (int64_t i = 0; i < k; i++) order[i] = (int32_t)i;
  const uint8_t* tp = tids.data();
  std::stable_sort(order.begin(), order.end(), [tp](int32_t a, int32_t b) {
    return memcmp(tp + (size_t)a * 16, tp + (size_t)b * 16, 16) < 0;
  });

  // one trace's TracesData body size: same rs/ss-run walk as the emit
  // pass, arithmetic only. [g0, g1) index into `order`.
  auto body_size = [&](int64_t g0, int64_t g1, uint64_t* lo, uint64_t* hi) {
    int64_t body = 0;
    int64_t a = g0;
    while (a < g1) {
      int32_t rs = sp_rs[order[a]];
      int64_t rs_body = rs_elen[rs];
      while (a < g1 && sp_rs[order[a]] == rs) {
        int32_t ss = sp_ss[order[a]];
        int64_t ss_body = ss_elen[ss];
        while (a < g1 && sp_ss[order[a]] == ss) {
          int32_t j = order[a];
          ss_body += 1 + vsize((uint64_t)sp_len[j]) + sp_len[j];
          if (st_ns[j] < *lo) *lo = st_ns[j];
          if (en_ns[j] > *hi) *hi = en_ns[j];
          a++;
        }
        rs_body += 1 + vsize((uint64_t)ss_body) + ss_body;
      }
      body += 1 + vsize((uint64_t)rs_body) + rs_body;
    }
    return body;
  };

  // pass A: total output size + trace count (capacity check up front so
  // the emit pass never has to be abandoned half-written); per-trace
  // results are cached so pass B never re-walks the sizes
  int64_t total_out = 0, n_tr = 0;
  std::vector<int64_t> tr_start, tr_body;
  std::vector<uint64_t> tr_lo, tr_hi;
  for (int64_t i = 0; i < k;) {
    int64_t g0 = i;
    while (i < k && memcmp(tp + (size_t)order[i] * 16,
                           tp + (size_t)order[g0] * 16, 16) == 0)
      i++;
    uint64_t lo = UINT64_MAX, hi = 0;
    int64_t body = body_size(g0, i, &lo, &hi);
    tr_start.push_back(g0);
    tr_body.push_back(body);
    tr_lo.push_back(lo);
    tr_hi.push_back(hi);
    total_out += 9 + body;
    n_tr++;
  }
  if (n_tr > cap_traces || total_out > cap_out) {
    counts[0] = n_tr;
    counts[1] = total_out;
    return 2;
  }

  // pass B: emit
  int64_t out_pos = 0;
  tr_start.push_back(k);  // sentinel: trace u spans order[tr_start[u] : tr_start[u+1]]
  for (int64_t u = 0; u < n_tr; u++) {
    int64_t g0 = tr_start[u], i = tr_start[u + 1];
    int64_t body = tr_body[u];
    uint64_t lo = tr_lo[u], hi = tr_hi[u];
    memcpy(tids_out + (size_t)u * 16, tp + (size_t)order[g0] * 16, 16);
    seg_off[u] = out_pos;
    seg_len[u] = 9 + body;
    uint64_t lo_s = lo == UINT64_MAX ? 0 : lo / 1000000000ull;
    // overflow-free exact ceil(hi / 1e9): end timestamps near 2^64 (the
    // scanner tolerates nonconformant varints) must not wrap to ~0 --
    // the Python oracle computes this with bignums
    uint64_t hi_s = hi ? (hi - 1) / 1000000000ull + 1 : 0;
    start_s_out[u] = (int64_t)lo_s;
    end_s_out[u] = (int64_t)hi_s;
    uint8_t* p = out + out_pos;
    *p++ = 0x01;
    uint32_t w32 = (uint32_t)lo_s;
    memcpy(p, &w32, 4); p += 4;
    w32 = (uint32_t)hi_s;
    memcpy(p, &w32, 4); p += 4;
    int64_t a = g0;
    while (a < i) {
      int32_t rs = sp_rs[order[a]];
      // recompute the run sizes inline (cheap arithmetic; avoids
      // buffering per-run size vectors between passes)
      int64_t rs_body = rs_elen[rs];
      {
        int64_t a2 = a;
        while (a2 < i && sp_rs[order[a2]] == rs) {
          int32_t ss = sp_ss[order[a2]];
          int64_t ss_body = ss_elen[ss];
          while (a2 < i && sp_ss[order[a2]] == ss) {
            ss_body += 1 + vsize((uint64_t)sp_len[order[a2]]) + sp_len[order[a2]];
            a2++;
          }
          rs_body += 1 + vsize((uint64_t)ss_body) + ss_body;
        }
      }
      *p++ = 0x0A;  // TracesData.resource_spans
      vput(&p, (uint64_t)rs_body);
      memcpy(p, env.data() + rs_eoff[rs], (size_t)rs_elen[rs]);
      p += rs_elen[rs];
      while (a < i && sp_rs[order[a]] == rs) {
        int32_t ss = sp_ss[order[a]];
        int64_t ss_body = ss_elen[ss];
        {
          int64_t a2 = a;
          while (a2 < i && sp_ss[order[a2]] == ss) {
            ss_body += 1 + vsize((uint64_t)sp_len[order[a2]]) + sp_len[order[a2]];
            a2++;
          }
        }
        *p++ = 0x12;  // ResourceSpans.scope_spans
        vput(&p, (uint64_t)ss_body);
        memcpy(p, senv.data() + ss_eoff[ss], (size_t)ss_elen[ss]);
        p += ss_elen[ss];
        while (a < i && sp_ss[order[a]] == ss) {
          int32_t j = order[a];
          *p++ = 0x12;  // ScopeSpans.spans
          vput(&p, (uint64_t)sp_len[j]);
          memcpy(p, buf + sp_off[j], (size_t)sp_len[j]);
          p += sp_len[j];
          a++;
        }
      }
    }
    out_pos += 9 + body;
  }
  counts[0] = n_tr;
  counts[1] = out_pos;
  return 0;
}

// ------------------------------------------------------------------- zstd

// Compress n chunks in parallel. in_offsets[i]..+in_lens[i] index into
// src; outputs go to dst at out_offsets (caller sizes dst with
// ZSTD_compressBound per chunk via vtpu_zstd_bound). Returns 0 on
// success; out_lens gets per-chunk compressed sizes.
int64_t vtpu_zstd_bound(int64_t n) { return (int64_t)ZSTD_compressBound((size_t)n); }

int vtpu_zstd_compress_batch(const uint8_t* src, const int64_t* in_offsets,
                             const int64_t* in_lens, uint8_t* dst,
                             const int64_t* out_offsets, int64_t* out_lens,
                             int n_chunks, int level, int n_threads) {
  std::atomic<int> next(0), failed(0);
  auto work = [&]() {
    ZSTD_CCtx* ctx = ZSTD_createCCtx();
    // advanced API: the one-shot ZSTD_compressCCtx treats level <= 0 as
    // "default", silently ignoring the fast negative levels
    ZSTD_CCtx_setParameter(ctx, ZSTD_c_compressionLevel, level);
    for (;;) {
      int i = next.fetch_add(1);
      if (i >= n_chunks) break;
      size_t r = ZSTD_compress2(ctx, dst + out_offsets[i],
                                (size_t)(vtpu_zstd_bound(in_lens[i])),
                                src + in_offsets[i], (size_t)in_lens[i]);
      if (ZSTD_isError(r)) {
        failed.store(1);
        break;
      }
      out_lens[i] = (int64_t)r;
    }
    ZSTD_freeCCtx(ctx);
  };
  int nt = std::max(1, std::min(n_threads, n_chunks));
  std::vector<std::thread> ts;
  for (int t = 1; t < nt; t++) ts.emplace_back(work);
  work();  // calling thread is worker 0 (no spawn cost when nt == 1)
  for (auto& t : ts) t.join();
  return failed.load();
}

// Decompress n chunks in parallel into caller-provided slots (exact
// decompressed sizes known from the column footer).
int vtpu_zstd_decompress_batch(const uint8_t* src, const int64_t* in_offsets,
                               const int64_t* in_lens, uint8_t* dst,
                               const int64_t* out_offsets, const int64_t* out_lens,
                               int n_chunks, int n_threads) {
  std::atomic<int> next(0), failed(0);
  auto work = [&]() {
    ZSTD_DCtx* ctx = ZSTD_createDCtx();
    for (;;) {
      int i = next.fetch_add(1);
      if (i >= n_chunks) break;
      size_t r = ZSTD_decompressDCtx(ctx, dst + out_offsets[i], (size_t)out_lens[i],
                                     src + in_offsets[i], (size_t)in_lens[i]);
      if (ZSTD_isError(r) || (int64_t)r != out_lens[i]) {
        failed.store(1);
        break;
      }
    }
    ZSTD_freeDCtx(ctx);
  };
  // calling thread is worker 0: single-threaded calls (1-core hosts,
  // small batches) pay zero spawn/join overhead
  int nt = std::max(1, std::min(n_threads, n_chunks));
  std::vector<std::thread> ts;
  for (int t = 1; t < nt; t++) ts.emplace_back(work);
  work();
  for (auto& t : ts) t.join();
  return failed.load();
}

// ----------------------------------------------------- snappy block codec
//
// Hand-rolled snappy + lz4 block codecs (reference: tempodb/backend/
// encoding.go carries both; klauspost's Go implementations are the
// upstream analog). Both are self-contained -- no external library --
// and ship threaded batch entry points shaped exactly like the zstd
// ones above, so the column layer's cold-read pipeline can decompress
// any registered codec's chunk batch on native threads. Formats are the
// standard public ones (snappy raw block framing, lz4 block format), so
// chunks interoperate with any other conformant implementation.

}  // pause extern "C": internal helpers use C++ linkage freely

// snappy raw block format: uvarint uncompressed length, then elements
// tagged by the low 2 bits (00 literal, 01/10/11 copies with 1/2/4-byte
// offsets). Compression works in 64 KiB fragments (like upstream) so a
// 16-bit position table suffices and every copy fits the 2-byte-offset
// form.
static const int kSnHashBits = 14;

static inline uint32_t sn_hash(uint32_t v) { return (v * 0x1e35a7bdu) >> (32 - kSnHashBits); }

static inline uint8_t* sn_emit_literal(uint8_t* p, const uint8_t* s, size_t len) {
  while (len > 0) {
    size_t l = len > 65536 ? 65536 : len;
    size_t n1 = l - 1;
    if (n1 < 60) {
      *p++ = (uint8_t)(n1 << 2);
    } else if (n1 < 256) {
      *p++ = 60 << 2;
      *p++ = (uint8_t)n1;
    } else {
      *p++ = 61 << 2;
      *p++ = (uint8_t)(n1 & 0xff);
      *p++ = (uint8_t)(n1 >> 8);
    }
    memcpy(p, s, l);
    p += l;
    s += l;
    len -= l;
  }
  return p;
}

static inline uint8_t* sn_emit_copy(uint8_t* p, size_t offset, size_t len) {
  while (len > 0) {
    size_t l = len > 64 ? 64 : len;
    *p++ = (uint8_t)(((l - 1) << 2) | 2);  // type 10: 2-byte offset
    *p++ = (uint8_t)(offset & 0xff);
    *p++ = (uint8_t)(offset >> 8);
    len -= l;
  }
  return p;
}

// one 64 KiB fragment: greedy 4-byte hash matching within the fragment
static uint8_t* sn_compress_fragment(const uint8_t* src, size_t n, uint8_t* p,
                                     uint16_t* table) {
  memset(table, 0, sizeof(uint16_t) << kSnHashBits);
  size_t i = 0, lit = 0;
  if (n >= 16) {
    size_t limit = n - 15;
    while (i < limit) {
      uint32_t v;
      memcpy(&v, src + i, 4);
      uint32_t h = sn_hash(v);
      size_t cand = table[h];
      table[h] = (uint16_t)i;
      uint32_t w;
      memcpy(&w, src + cand, 4);
      if (cand < i && w == v) {
        size_t len = 4;
        while (i + len < n && src[cand + len] == src[i + len]) len++;
        p = sn_emit_literal(p, src + lit, i - lit);
        p = sn_emit_copy(p, i - cand, len);
        i += len;
        lit = i;
      } else {
        i++;
      }
    }
  }
  return sn_emit_literal(p, src + lit, n - lit);
}

static size_t snappy_compress_one(const uint8_t* src, size_t n, uint8_t* dst,
                                  uint16_t* table) {
  uint8_t* p = dst;
  uint64_t v = n;
  while (v >= 128) {
    *p++ = (uint8_t)(v | 0x80);
    v >>= 7;
  }
  *p++ = (uint8_t)v;
  for (size_t off = 0; off < n; off += 65536) {
    size_t frag = n - off > 65536 ? 65536 : n - off;
    p = sn_compress_fragment(src + off, frag, p, table);
  }
  return (size_t)(p - dst);
}

static int snappy_decompress_one(const uint8_t* src, size_t n, uint8_t* dst,
                                 size_t dn) {
  size_t pos = 0;
  uint64_t len = 0;
  int shift = 0;
  for (;;) {
    if (pos >= n || shift > 35) return 1;
    uint8_t b = src[pos++];
    len |= (uint64_t)(b & 0x7f) << shift;
    if (!(b & 0x80)) break;
    shift += 7;
  }
  if (len != (uint64_t)dn) return 1;
  size_t d = 0;
  while (pos < n) {
    uint8_t tag = src[pos++];
    int type = tag & 3;
    if (type == 0) {
      size_t l = (size_t)(tag >> 2) + 1;
      if (l > 60) {
        int extra = (int)l - 60;  // 1..4 length bytes, little endian
        if (pos + (size_t)extra > n) return 1;
        l = 0;
        for (int k = 0; k < extra; k++) l |= (size_t)src[pos + k] << (8 * k);
        l += 1;
        pos += (size_t)extra;
      }
      if (pos + l > n || d + l > dn) return 1;
      memcpy(dst + d, src + pos, l);
      pos += l;
      d += l;
      continue;
    }
    size_t l, off;
    if (type == 1) {
      if (pos >= n) return 1;
      l = (size_t)((tag >> 2) & 7) + 4;
      off = ((size_t)(tag >> 5) << 8) | src[pos++];
    } else if (type == 2) {
      if (pos + 2 > n) return 1;
      l = (size_t)(tag >> 2) + 1;
      off = (size_t)src[pos] | ((size_t)src[pos + 1] << 8);
      pos += 2;
    } else {
      if (pos + 4 > n) return 1;
      l = (size_t)(tag >> 2) + 1;
      off = (size_t)src[pos] | ((size_t)src[pos + 1] << 8) |
            ((size_t)src[pos + 2] << 16) | ((size_t)src[pos + 3] << 24);
      pos += 4;
    }
    if (off == 0 || off > d || d + l > dn) return 1;
    const uint8_t* s = dst + d - off;
    if (off >= l) {
      memcpy(dst + d, s, l);
    } else {
      for (size_t k = 0; k < l; k++) dst[d + k] = s[k];  // overlapped RLE copy
    }
    d += l;
  }
  return d == dn ? 0 : 1;
}

// lz4 block format: sequences of [token][lit-ext][literals][2B offset]
// [match-ext]; the final sequence is literals-only. End-of-block rules
// honored: the last match starts >= 12 bytes before the end and never
// covers the last 5 bytes.
static inline uint32_t lz4_hash(uint32_t v) { return (v * 2654435761u) >> 16; }

static size_t lz4_compress_one(const uint8_t* src, size_t n, uint8_t* dst,
                               int32_t* table) {
  memset(table, 0xff, sizeof(int32_t) << 16);  // -1 = empty
  uint8_t* p = dst;
  size_t i = 0, lit = 0;
  if (n > 16) {
    size_t mflimit = n - 12;  // last match must start before here
    while (i < mflimit) {
      uint32_t v;
      memcpy(&v, src + i, 4);
      uint32_t h = lz4_hash(v);
      int32_t cand = table[h];
      table[h] = (int32_t)i;
      uint32_t w = 0;
      if (cand >= 0) memcpy(&w, src + cand, 4);
      if (cand >= 0 && w == v && i - (size_t)cand <= 65535) {
        size_t maxlen = n - 5 - i;  // never cover the last 5 bytes
        size_t len = 4;
        while (len < maxlen && src[(size_t)cand + len] == src[i + len]) len++;
        size_t ll = i - lit, ml = len - 4;
        uint8_t* tok = p++;
        if (ll >= 15) {
          *tok = 0xF0;
          size_t r = ll - 15;
          while (r >= 255) {
            *p++ = 255;
            r -= 255;
          }
          *p++ = (uint8_t)r;
        } else {
          *tok = (uint8_t)(ll << 4);
        }
        memcpy(p, src + lit, ll);
        p += ll;
        size_t off = i - (size_t)cand;
        *p++ = (uint8_t)(off & 0xff);
        *p++ = (uint8_t)(off >> 8);
        if (ml >= 15) {
          *tok |= 0x0F;
          size_t r = ml - 15;
          while (r >= 255) {
            *p++ = 255;
            r -= 255;
          }
          *p++ = (uint8_t)r;
        } else {
          *tok |= (uint8_t)ml;
        }
        i += len;
        lit = i;
      } else {
        i++;
      }
    }
  }
  size_t ll = n - lit;  // final literals-only sequence
  uint8_t* tok = p++;
  if (ll >= 15) {
    *tok = 0xF0;
    size_t r = ll - 15;
    while (r >= 255) {
      *p++ = 255;
      r -= 255;
    }
    *p++ = (uint8_t)r;
  } else {
    *tok = (uint8_t)(ll << 4);
  }
  memcpy(p, src + lit, ll);
  p += ll;
  return (size_t)(p - dst);
}

static int lz4_decompress_one(const uint8_t* src, size_t n, uint8_t* dst,
                              size_t dn) {
  size_t pos = 0, d = 0;
  if (n == 0) return dn == 0 ? 0 : 1;
  while (pos < n) {
    uint8_t tok = src[pos++];
    size_t ll = (size_t)(tok >> 4);
    if (ll == 15) {
      uint8_t b;
      do {
        if (pos >= n) return 1;
        b = src[pos++];
        ll += b;
      } while (b == 255);
    }
    if (pos + ll > n || d + ll > dn) return 1;
    memcpy(dst + d, src + pos, ll);
    pos += ll;
    d += ll;
    if (pos == n) break;  // final literals-only sequence
    if (pos + 2 > n) return 1;
    size_t off = (size_t)src[pos] | ((size_t)src[pos + 1] << 8);
    pos += 2;
    size_t ml = (size_t)(tok & 15);
    if (ml == 15) {
      uint8_t b;
      do {
        if (pos >= n) return 1;
        b = src[pos++];
        ml += b;
      } while (b == 255);
    }
    ml += 4;
    if (off == 0 || off > d || d + ml > dn) return 1;
    const uint8_t* s = dst + d - off;
    if (off >= ml) {
      memcpy(dst + d, s, ml);
    } else {
      for (size_t k = 0; k < ml; k++) dst[d + k] = s[k];
    }
    d += ml;
  }
  return d == dn ? 0 : 1;
}

extern "C" {

// worst-case bounds (callers size dst per chunk, like vtpu_zstd_bound)
int64_t vtpu_snappy_bound(int64_t n) { return 32 + n + n / 6; }
int64_t vtpu_lz4_bound(int64_t n) { return 16 + n + n / 255; }

int vtpu_snappy_compress_batch(const uint8_t* src, const int64_t* in_offsets,
                               const int64_t* in_lens, uint8_t* dst,
                               const int64_t* out_offsets, int64_t* out_lens,
                               int n_chunks, int n_threads) {
  std::atomic<int> next(0);
  auto work = [&]() {
    std::vector<uint16_t> table((size_t)1 << kSnHashBits);
    for (;;) {
      int i = next.fetch_add(1);
      if (i >= n_chunks) break;
      out_lens[i] = (int64_t)snappy_compress_one(
          src + in_offsets[i], (size_t)in_lens[i], dst + out_offsets[i],
          table.data());
    }
  };
  int nt = std::max(1, std::min(n_threads, n_chunks));
  std::vector<std::thread> ts;
  for (int t = 1; t < nt; t++) ts.emplace_back(work);
  work();  // calling thread is worker 0 (no spawn cost when nt == 1)
  for (auto& t : ts) t.join();
  return 0;
}

int vtpu_snappy_decompress_batch(const uint8_t* src, const int64_t* in_offsets,
                                 const int64_t* in_lens, uint8_t* dst,
                                 const int64_t* out_offsets,
                                 const int64_t* out_lens, int n_chunks,
                                 int n_threads) {
  std::atomic<int> next(0), failed(0);
  auto work = [&]() {
    for (;;) {
      int i = next.fetch_add(1);
      if (i >= n_chunks) break;
      if (snappy_decompress_one(src + in_offsets[i], (size_t)in_lens[i],
                                dst + out_offsets[i], (size_t)out_lens[i])) {
        failed.store(1);
        break;
      }
    }
  };
  int nt = std::max(1, std::min(n_threads, n_chunks));
  std::vector<std::thread> ts;
  for (int t = 1; t < nt; t++) ts.emplace_back(work);
  work();
  for (auto& t : ts) t.join();
  return failed.load();
}

int vtpu_lz4_compress_batch(const uint8_t* src, const int64_t* in_offsets,
                            const int64_t* in_lens, uint8_t* dst,
                            const int64_t* out_offsets, int64_t* out_lens,
                            int n_chunks, int n_threads) {
  std::atomic<int> next(0);
  auto work = [&]() {
    std::vector<int32_t> table((size_t)1 << 16);
    for (;;) {
      int i = next.fetch_add(1);
      if (i >= n_chunks) break;
      out_lens[i] = (int64_t)lz4_compress_one(src + in_offsets[i],
                                              (size_t)in_lens[i],
                                              dst + out_offsets[i],
                                              table.data());
    }
  };
  int nt = std::max(1, std::min(n_threads, n_chunks));
  std::vector<std::thread> ts;
  for (int t = 1; t < nt; t++) ts.emplace_back(work);
  work();
  for (auto& t : ts) t.join();
  return 0;
}

int vtpu_lz4_decompress_batch(const uint8_t* src, const int64_t* in_offsets,
                              const int64_t* in_lens, uint8_t* dst,
                              const int64_t* out_offsets,
                              const int64_t* out_lens, int n_chunks,
                              int n_threads) {
  std::atomic<int> next(0), failed(0);
  auto work = [&]() {
    for (;;) {
      int i = next.fetch_add(1);
      if (i >= n_chunks) break;
      if (lz4_decompress_one(src + in_offsets[i], (size_t)in_lens[i],
                             dst + out_offsets[i], (size_t)out_lens[i])) {
        failed.store(1);
        break;
      }
    }
  };
  int nt = std::max(1, std::min(n_threads, n_chunks));
  std::vector<std::thread> ts;
  for (int t = 1; t < nt; t++) ts.emplace_back(work);
  work();
  for (auto& t : ts) t.join();
  return failed.load();
}

// ---------------------------------------------------------- run gather

// Copy n_runs row ranges from src to dst: run i moves lens[i] rows from
// src row src_offs[i] to dst row dst_offs[i]; rows are itemsize bytes.
// The compaction merge's unit of data movement (columnar_compact
// _assemble): one memcpy per run instead of a per-ELEMENT numpy fancy
// index, so the index arrays (8 bytes/row/column) never exist and the
// traffic is just src+dst.
void vtpu_gather_runs(const uint8_t* src, uint8_t* dst,
                      const int64_t* src_offs, const int64_t* dst_offs,
                      const int64_t* lens, int64_t n_runs, int64_t itemsize) {
  for (int64_t i = 0; i < n_runs; i++) {
    memcpy(dst + dst_offs[i] * itemsize, src + src_offs[i] * itemsize,
           (size_t)(lens[i] * itemsize));
  }
}

// Same, but each run reads from an absolute source ADDRESS: callers
// with K source arrays order runs by destination (dst writes stream
// sequentially, each source reads stream too) and pass per-run
// src pointers computed host-side. dst_offs/lens in rows.
// K-way merges read each run from a RANDOM source position while dst
// streams sequentially: per-run cost is one DRAM round trip (~100 ns),
// which dominates the copy itself for trace-axis runs (one 4-byte row).
// Prefetching a few runs ahead overlaps those misses.
#define VTPU_RUN_PREFETCH 8

void vtpu_gather_runs_addr(const int64_t* src_addrs, uint8_t* dst,
                           const int64_t* dst_offs, const int64_t* lens,
                           int64_t n_runs, int64_t itemsize) {
  // runs are typically a handful of rows (one trace's spans; ONE row on
  // the trace axis) -- glibc memcpy's dispatch overhead dominates at
  // that size, so 4/8-byte rows take a plain word loop instead
  if (itemsize == 4) {
    uint32_t* d32 = (uint32_t*)dst;
    for (int64_t i = 0; i < n_runs; i++) {
      if (i + VTPU_RUN_PREFETCH < n_runs)
        __builtin_prefetch((const void*)(uintptr_t)src_addrs[i + VTPU_RUN_PREFETCH], 0, 1);
      const uint32_t* s = (const uint32_t*)(uintptr_t)src_addrs[i];
      uint32_t* d = d32 + dst_offs[i];
      int64_t n = lens[i];
      for (int64_t j = 0; j < n; j++) d[j] = s[j];
    }
    return;
  }
  if (itemsize == 8) {
    uint64_t* d64 = (uint64_t*)dst;
    for (int64_t i = 0; i < n_runs; i++) {
      if (i + VTPU_RUN_PREFETCH < n_runs)
        __builtin_prefetch((const void*)(uintptr_t)src_addrs[i + VTPU_RUN_PREFETCH], 0, 1);
      const uint64_t* s = (const uint64_t*)(uintptr_t)src_addrs[i];
      uint64_t* d = d64 + dst_offs[i];
      int64_t n = lens[i];
      for (int64_t j = 0; j < n; j++) d[j] = s[j];
    }
    return;
  }
  for (int64_t i = 0; i < n_runs; i++) {
    if (i + VTPU_RUN_PREFETCH < n_runs)
      __builtin_prefetch((const void*)(uintptr_t)src_addrs[i + VTPU_RUN_PREFETCH], 0, 1);
    memcpy(dst + dst_offs[i] * itemsize, (const void*)(uintptr_t)src_addrs[i],
           (size_t)(lens[i] * itemsize));
  }
}

// Gather runs of an int32 code column while remapping codes through a
// lookup table (negative codes = "absent" sentinels pass through):
// compaction's dictionary re-encode fused into the merge copy, so the
// remap costs no extra memory pass. remap_addrs[i]/remap_lens[i] give
// run i's source remap table. Returns the count of out-of-range codes
// (corrupt input); non-zero means the caller must redo via its checked
// fallback -- the kernel writes such codes through unchanged rather
// than reading past the table.
int64_t vtpu_gather_runs_remap(const int64_t* src_addrs, int32_t* dst,
                               const int64_t* dst_offs, const int64_t* lens,
                               const int64_t* remap_addrs,
                               const int64_t* remap_lens, int64_t n_runs) {
  int64_t oob = 0;
  for (int64_t i = 0; i < n_runs; i++) {
    if (i + VTPU_RUN_PREFETCH < n_runs)
      __builtin_prefetch((const void*)(uintptr_t)src_addrs[i + VTPU_RUN_PREFETCH], 0, 1);
    const int32_t* s = (const int32_t*)(uintptr_t)src_addrs[i];
    const int32_t* remap = (const int32_t*)(uintptr_t)remap_addrs[i];
    const int64_t rlen = remap_lens[i];
    int32_t* d = dst + dst_offs[i];
    int64_t n = lens[i];
    for (int64_t j = 0; j < n; j++) {
      int32_t v = s[j];
      if (v >= 0) {
        if (v < rlen) {
          d[j] = remap[v];
        } else {
          d[j] = v;
          oob++;
        }
      } else {
        d[j] = v;
      }
    }
  }
  return oob;
}

// ------------------------------------------------------------ search eval
//
// Host filter primitives for the one-shot/cold search engine
// (ops/hostfilter.py): single-pass C loops replacing multi-pass numpy
// (mask materialization + astype + concatenate + reduceat). The repo's
// counterpart of the reference's hand-tuned parquetquery predicate
// loops (pkg/parquetquery/predicates.go), shaped for a 1-2 core host
// feeding a TPU: memory-bandwidth-bound streaming, no allocation.

// op codes shared with tempo_tpu/native/__init__.py mask_cmp()
enum { CMP_EQ = 0, CMP_NE, CMP_LT, CMP_LE, CMP_GT, CMP_GE, CMP_RANGE, CMP_NE_PRESENT };

}  // pause extern "C": templates cannot carry C language linkage

template <typename T>
static inline void mask_cmp_t(const T* x, int64_t n, int op, int64_t a64,
                              int64_t b64, uint8_t* out) {
  const T a = (T)a64, b = (T)b64;
  switch (op) {
    case CMP_EQ: for (int64_t i = 0; i < n; i++) out[i] = x[i] == a; break;
    case CMP_NE: for (int64_t i = 0; i < n; i++) out[i] = x[i] != a; break;
    case CMP_LT: for (int64_t i = 0; i < n; i++) out[i] = x[i] < a; break;
    case CMP_LE: for (int64_t i = 0; i < n; i++) out[i] = x[i] <= a; break;
    case CMP_GT: for (int64_t i = 0; i < n; i++) out[i] = x[i] > a; break;
    case CMP_GE: for (int64_t i = 0; i < n; i++) out[i] = x[i] >= a; break;
    case CMP_RANGE:
      for (int64_t i = 0; i < n; i++) out[i] = x[i] >= a && x[i] <= b;
      break;
    case CMP_NE_PRESENT:
      for (int64_t i = 0; i < n; i++) out[i] = x[i] != a && x[i] >= 0;
      break;
  }
}

extern "C" {

void vtpu_mask_cmp_i32(const int32_t* x, int64_t n, int op, int64_t a,
                       int64_t b, uint8_t* out) {
  mask_cmp_t<int32_t>(x, n, op, a, b, out);
}

void vtpu_mask_cmp_i64(const int64_t* x, int64_t n, int op, int64_t a,
                       int64_t b, uint8_t* out) {
  mask_cmp_t<int64_t>(x, n, op, a, b, out);
}

// res->span mask through a lookup table: out[j] = lut[idx[j]] for valid
// indices, 0 for negative/out-of-range (absent-resource sentinel).
void vtpu_mask_lut_i32(const int32_t* idx, int64_t n, const uint8_t* lut,
                       int64_t n_lut, uint8_t* out) {
  for (int64_t j = 0; j < n; j++) {
    const int32_t v = idx[j];
    out[j] = ((uint32_t)v < (uint32_t)n_lut) ? lut[v] : 0;
  }
}

// Matched spans per trace: out[t] = sum(mask[off[t] .. off[t+1])), with
// offsets clipped to n_spans (sliced row-group shards clip trailing
// offsets legally).
void vtpu_seg_count_mask(const uint8_t* mask, const int32_t* span_off,
                         int64_t n_traces, int64_t n_spans, int32_t* out) {
  for (int64_t t = 0; t < n_traces; t++) {
    int64_t lo = span_off[t], hi = span_off[t + 1];
    if (lo > n_spans) lo = n_spans;
    if (hi > n_spans) hi = n_spans;
    int32_t c = 0;
    for (int64_t j = lo; j < hi; j++) c += mask[j];
    out[t] = c;
  }
}

// Weighted variant: rows carry fold weights (the tres membership axis,
// where each entry stands for weight[j] spans -- db/search._host_eval).
// Replaces numpy's pad+reduceat, which costs ~5x this linear scan.
void vtpu_seg_weighted_count(const uint8_t* mask, const int32_t* weights,
                             const int32_t* span_off, int64_t n_traces,
                             int64_t n_spans, int64_t* out) {
  for (int64_t t = 0; t < n_traces; t++) {
    int64_t lo = span_off[t], hi = span_off[t + 1];
    if (lo > n_spans) lo = n_spans;
    if (hi > n_spans) hi = n_spans;
    int64_t c = 0;
    for (int64_t j = lo; j < hi; j++) c += mask[j] ? weights[j] : 0;
    out[t] = c;
  }
}

// --------------------------------------------------------- span metrics

// Fused span-metrics fold (the metrics-generator's per-collection
// reduce): one pass scattering into per-series histogram + latency-sum
// accumulators. The (series x bucket) table is ~KBs, so the random
// scatters stay in cache; bucket search is a linear scan (<= ~16
// edges, branch-predictable). Matches numpy's
// searchsorted(edges, dur, side='left') bucketing exactly.
void vtpu_span_metrics(const int32_t* sid, const float* dur, int64_t n,
                       const float* edges, int n_edges, int64_t n_series,
                       int64_t* hist, double* lat_sum) {
  const int nb = n_edges + 1;
  for (int64_t i = 0; i < n; i++) {
    const int32_t s = sid[i];
    if ((uint64_t)s >= (uint64_t)n_series) continue;
    const float d = dur[i];
    int b = 0;
    // !(d <= e) instead of (d > e): NaN then falls through to the LAST
    // bucket, matching searchsorted's "NaN sorts after everything"
    while (b < n_edges && !(d <= edges[b])) b++;
    hist[(int64_t)s * nb + b]++;
    lat_sum[s] += (double)d;
  }
}

// ------------------------------------------------- slot-major placement

// One generic-attribute value column of a staged slice, placed
// slot-major in one pass (ops/stage._SlotLayout.place): `out` is K
// planes of n_spans_b -- plane j holds every span's j-th row -- and
// then the rows beyond a span's K-th in row order (the overflow).
// Rows are grouped by owner in ascending span order; an owner outside
// [span_base, span_base + n_spans) belongs to the edge span. Every
// element of `out` is written exactly once: a row's value, or `pad`
// where no row lands (a span's missing slots, the spans after n_spans,
// the overflow's tail). Elements are 4 bytes whatever their type.
// Returns the overflow rows written, or -1 (out is then unspecified,
// the caller places with numpy) when owners descend or the arguments
// do not fit `out`.
int64_t vtpu_slot_place_u32(const int32_t* owners, int64_t n_rows,
                            int64_t span_base, int64_t n_spans,
                            int64_t n_spans_b, int64_t k,
                            const uint32_t* src, uint32_t* out,
                            int64_t out_len, uint32_t pad) {
  if (k < 0 || n_spans_b < 1 || n_spans > n_spans_b ||
      k * n_spans_b > out_len)
    return -1;
  const int64_t hi = n_spans > 0 ? n_spans - 1 : 0;
  uint32_t* tail = out + k * n_spans_b;
  const int64_t tail_len = out_len - k * n_spans_b;
  int64_t n_over = 0;
  // the span whose run is open and the rows of it seen: plane j is
  // written up to (excluding) span cur + 1 where j < slot, else cur
  int64_t cur = 0, slot = 0;
  auto pad_until = [&](int64_t span) {
    for (int64_t j = 0; j < k; j++) {
      uint32_t* plane = out + j * n_spans_b;
      std::fill(plane + (j < slot ? cur + 1 : cur), plane + span, pad);
    }
  };
  for (int64_t i = 0; i < n_rows; i++) {
    int64_t o = (int64_t)owners[i] - span_base;
    o = o < 0 ? 0 : (o > hi ? hi : o);
    if (o != cur) {
      if (o < cur) return -1;
      pad_until(o);
      cur = o;
      slot = 0;
    }
    if (slot < k) {
      out[slot * n_spans_b + o] = src[i];
    } else {
      if (n_over >= tail_len) return -1;
      tail[n_over++] = src[i];
    }
    slot++;
  }
  pad_until(n_spans_b);
  std::fill(tail + n_over, tail + tail_len, pad);
  return n_over;
}

// ------------------------------------------------------- dictionary union

// K-way merge of K SORTED string tables (compaction's dictionary union,
// the role of the reference's per-row dictionary re-encode in
// vparquet/compactor.go). Inputs are flattened: source i has counts[i]
// strings; its offsets (counts[i]+1 uint32, 0-based into its own blob)
// start at off_starts[i] in all_offsets, its blob at blob_starts[i] in
// all_blobs. Outputs: merged offsets/blob (caller-allocated at summed
// capacity) and, for every input string in source order, its code in
// the merged table (the per-source remap gather compaction applies to
// every code column). Returns the merged string count, or -1 on error.
int64_t vtpu_dict_union(int64_t n_src, const int64_t* counts,
                        const uint32_t* all_offsets, const int64_t* off_starts,
                        const uint8_t* all_blobs, const int64_t* blob_starts,
                        uint32_t* out_offsets, uint8_t* out_blob,
                        int32_t* remap_flat, const int64_t* remap_starts,
                        int64_t* out_blob_len) {
  struct Head {
    const uint8_t* p;
    uint32_t len;
    int32_t src;
    int64_t idx;
  };
  auto str_at = [&](int64_t s, int64_t i, uint32_t* len) -> const uint8_t* {
    const uint32_t* offs = all_offsets + off_starts[s];
    *len = offs[i + 1] - offs[i];
    return all_blobs + blob_starts[s] + offs[i];
  };
  auto less = [](const Head& a, const Head& b) {
    // min-heap by string (then source for stability): std::push_heap
    // builds a max-heap, so invert
    int c = memcmp(a.p, b.p, a.len < b.len ? a.len : b.len);
    if (c != 0) return c > 0;
    if (a.len != b.len) return a.len > b.len;
    return a.src > b.src;
  };
  std::vector<Head> heap;
  heap.reserve((size_t)n_src);
  for (int64_t s = 0; s < n_src; s++) {
    if (counts[s] > 0) {
      Head h;
      h.p = str_at(s, 0, &h.len);
      h.src = (int32_t)s;
      h.idx = 0;
      heap.push_back(h);
    }
  }
  std::make_heap(heap.begin(), heap.end(), less);
  int64_t n_out = 0, blob_pos = 0;
  const uint8_t* last_p = nullptr;
  uint32_t last_len = 0;
  out_offsets[0] = 0;
  while (!heap.empty()) {
    std::pop_heap(heap.begin(), heap.end(), less);
    Head h = heap.back();
    heap.pop_back();
    bool is_dup = last_p != nullptr && h.len == last_len &&
                  memcmp(h.p, last_p, h.len) == 0;
    if (!is_dup) {
      memcpy(out_blob + blob_pos, h.p, h.len);
      blob_pos += h.len;
      n_out++;
      out_offsets[n_out] = (uint32_t)blob_pos;
      last_p = h.p;
      last_len = h.len;
    }
    remap_flat[remap_starts[h.src] + h.idx] = (int32_t)(n_out - 1);
    if (h.idx + 1 < counts[h.src]) {
      Head nh;
      nh.p = str_at(h.src, h.idx + 1, &nh.len);
      nh.src = h.src;
      nh.idx = h.idx + 1;
      heap.push_back(nh);
      std::push_heap(heap.begin(), heap.end(), less);
    }
  }
  *out_blob_len = blob_pos;
  return n_out;
}

}  // extern "C"
