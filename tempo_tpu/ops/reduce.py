"""Segmented reduces for the metrics-generator: span-metrics as one
fused device pass (BASELINE config #5).

The reference updates per-series counters span by span
(modules/generator/processor/spanmetrics/spanmetrics.go:79-96 +
registry histogram.go); here a collection cycle's buffered spans fold
into (calls, latency_sum, latency_histogram) with three segment reduces
in one jitted program: series ids are the segments, the histogram
scatter uses a combined (series, bucket) index.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .device import bucket as pow2
from .device import scoped


@partial(jax.jit, static_argnames=("n_series_b", "n_buckets"))
@scoped("reduce")
def _reduce_kernel(sid, dur, n_valid, edges, n_series_b: int, n_buckets: int):
    """sid: (N,) int32 (pad: n_series_b), dur: (N,) f32, edges: (n_buckets-1,)
    -> calls (S,), lat_sum (S,), hist (S, n_buckets)."""
    valid = jnp.arange(sid.shape[0]) < n_valid
    seg = jnp.where(valid, sid, n_series_b)
    ones = valid.astype(jnp.int32)
    calls = jax.ops.segment_sum(ones, seg, num_segments=n_series_b + 1)[:n_series_b]
    lat_sum = jax.ops.segment_sum(jnp.where(valid, dur, 0.0), seg,
                                  num_segments=n_series_b + 1)[:n_series_b]
    bidx = jnp.searchsorted(edges, dur)  # 0..n_buckets-1
    combo = jnp.where(valid, seg * n_buckets + bidx, n_series_b * n_buckets)
    hist = jax.ops.segment_sum(ones, combo, num_segments=n_series_b * n_buckets + 1)[:-1]
    return calls, lat_sum, hist.reshape(n_series_b, n_buckets)


def _reduce_host(sid: np.ndarray, dur_s: np.ndarray, n_series: int,
                 bucket_edges: tuple):
    """Host twin of the device kernel: one fused native pass (the
    series x bucket table stays cache-resident), numpy fallback of one
    searchsorted + two bincounts. Exact same outputs."""
    edges = np.asarray(bucket_edges, np.float32)
    from ..native import span_metrics_fold

    out = span_metrics_fold(np.ascontiguousarray(sid, np.int32),
                            np.ascontiguousarray(dur_s, np.float32),
                            edges, n_series)
    if out is not None:
        hist, lsum = out
        return hist.sum(axis=1).astype(np.int64), lsum, hist
    nb = len(bucket_edges) + 1
    bidx = np.searchsorted(edges, dur_s.astype(np.float32))
    combo = sid.astype(np.int64) * nb + bidx
    hist = np.bincount(combo, minlength=n_series * nb)[: n_series * nb]
    hist = hist.reshape(n_series, nb)
    lsum = np.bincount(sid, weights=dur_s.astype(np.float64), minlength=n_series)[:n_series]
    return (hist.sum(axis=1).astype(np.int64), lsum.astype(np.float64),
            hist.astype(np.int64))


@partial(jax.jit, static_argnames=("n_edges_b", "n_buckets"))
@scoped("edge_reduce")
def _edge_reduce_kernel(eid, cdur, sdur, failed, n_valid, edges,
                        n_edges_b: int, n_buckets: int):
    """One fused program for a window's completed service-graph edges:
    eid (N,) int32 (pad: n_edges_b), cdur/sdur (N,) f32, failed (N,)
    int32 -> counts (E,), failed_counts (E,), client_sum (E,),
    server_sum (E,), client_hist (E, nb), server_hist (E, nb). Six
    segment reduces sharing one upload instead of the legacy two
    span_metrics launches + host bincount."""
    valid = jnp.arange(eid.shape[0]) < n_valid
    seg = jnp.where(valid, eid, n_edges_b)
    ones = valid.astype(jnp.int32)
    ns = n_edges_b + 1
    counts = jax.ops.segment_sum(ones, seg, num_segments=ns)[:n_edges_b]
    fcounts = jax.ops.segment_sum(jnp.where(valid, failed, 0), seg,
                                  num_segments=ns)[:n_edges_b]
    csum = jax.ops.segment_sum(jnp.where(valid, cdur, 0.0), seg,
                               num_segments=ns)[:n_edges_b]
    ssum = jax.ops.segment_sum(jnp.where(valid, sdur, 0.0), seg,
                               num_segments=ns)[:n_edges_b]
    nhist = n_edges_b * n_buckets + 1
    ccombo = jnp.where(valid, seg * n_buckets + jnp.searchsorted(edges, cdur),
                       n_edges_b * n_buckets)
    scombo = jnp.where(valid, seg * n_buckets + jnp.searchsorted(edges, sdur),
                       n_edges_b * n_buckets)
    chist = jax.ops.segment_sum(ones, ccombo, num_segments=nhist)[:-1]
    shist = jax.ops.segment_sum(ones, scombo, num_segments=nhist)[:-1]
    return (counts, fcounts, csum, ssum,
            chist.reshape(n_edges_b, n_buckets),
            shist.reshape(n_edges_b, n_buckets))


def _edge_reduce_host(eid: np.ndarray, cdur: np.ndarray, sdur: np.ndarray,
                      failed: np.ndarray, n_edges: int, bucket_edges: tuple):
    """Host twin of the edge kernel: composes the span-metrics host fold
    per side plus a failed bincount -- numerically EXACTLY the legacy
    ServiceGraphsProcessor.collect sequence, which is what makes the
    streaming-vs-legacy differential bit-for-bit."""
    counts, csum, chist = _reduce_host(eid, cdur, n_edges, bucket_edges)
    _, ssum, shist = _reduce_host(eid, sdur, n_edges, bucket_edges)
    fcounts = np.bincount(eid[failed.astype(bool)],
                          minlength=n_edges)[:n_edges].astype(np.int64)
    return counts, fcounts, csum, ssum, chist, shist


def edge_metrics_reduce(eid: np.ndarray, cdur: np.ndarray, sdur: np.ndarray,
                        failed: np.ndarray, n_edges: int, bucket_edges: tuple):
    """-> (counts, failed_counts, client_sum, server_sum, client_hist,
    server_hist) per edge id, as numpy. Same engine policy as
    span_metrics_reduce: host fold when the measured link round trip
    is over 2 ms, one fused device program otherwise."""
    n = eid.shape[0]
    nb = len(bucket_edges) + 1
    if n == 0 or n_edges == 0:
        z = np.zeros(n_edges, np.int64)
        zf = np.zeros(n_edges, np.float64)
        zh = np.zeros((n_edges, nb), np.int64)
        return z, z.copy(), zf, zf.copy(), zh, zh.copy()
    from ..util.kerneltel import TEL
    from ..util.linkcost import link_rtt_ms

    if link_rtt_ms() > 2.0:
        TEL.record_routing("edge_reduce", "host", "link_rtt")
        return _edge_reduce_host(eid, cdur, sdur, failed, n_edges, bucket_edges)
    TEL.record_routing("edge_reduce", "device", "link_fast")
    Np = pow2(n)
    Eb = pow2(n_edges)
    eid_p = np.full(Np, Eb, dtype=np.int32)
    eid_p[:n] = eid
    cdur_p = np.zeros(Np, dtype=np.float32)
    cdur_p[:n] = cdur
    sdur_p = np.zeros(Np, dtype=np.float32)
    sdur_p[:n] = sdur
    failed_p = np.zeros(Np, dtype=np.int32)
    failed_p[:n] = failed.astype(np.int32)

    with TEL.launch("edge_reduce", ("edge_reduce", Np, Eb, nb), Np):
        # host scalars in, host slices out: an eager jnp.int32() or a
        # slice of a device array is a jitted program of its own, and the
        # slice compiles once per distinct edge count (a traced write
        # window read 0.9 s of PjitFunction(dynamic_slice), PR 23)
        counts, fcounts, csum, ssum, chist, shist = _edge_reduce_kernel(
            jnp.asarray(eid_p), jnp.asarray(cdur_p), jnp.asarray(sdur_p),
            jnp.asarray(failed_p), np.int32(n),
            jnp.asarray(np.asarray(bucket_edges, np.float32)), Eb, nb
        )
        out = (np.asarray(counts)[:n_edges].astype(np.int64),
               np.asarray(fcounts)[:n_edges].astype(np.int64),
               np.asarray(csum)[:n_edges].astype(np.float64),
               np.asarray(ssum)[:n_edges].astype(np.float64),
               np.asarray(chist)[:n_edges].astype(np.int64),
               np.asarray(shist)[:n_edges].astype(np.int64))
    return out


def span_metrics_reduce(sid: np.ndarray, dur_s: np.ndarray, n_series: int,
                        bucket_edges: tuple) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """-> (calls (n_series,), latency_sum (n_series,),
    histogram (n_series, len(edges)+1)) as numpy.

    Engine choice mirrors search: the device fold is one fused program
    but costs an upload of 8 bytes/span plus sync round trips -- above a
    2 ms measured link round trip (util/linkcost.py) the host bincount
    fold is taken, below it the device."""
    n = sid.shape[0]
    if n == 0 or n_series == 0:
        nb = len(bucket_edges) + 1
        return (np.zeros(n_series, np.int64), np.zeros(n_series, np.float64),
                np.zeros((n_series, nb), np.int64))
    from ..util.kerneltel import TEL
    from ..util.linkcost import link_rtt_ms

    if link_rtt_ms() > 2.0:
        TEL.record_routing("spanmetrics", "host", "link_rtt")
        return _reduce_host(sid, dur_s, n_series, bucket_edges)
    TEL.record_routing("spanmetrics", "device", "link_fast")
    nb = len(bucket_edges) + 1
    Np = pow2(n)
    Sb = pow2(n_series)
    sid_p = np.full(Np, Sb, dtype=np.int32)
    sid_p[:n] = sid
    dur_p = np.zeros(Np, dtype=np.float32)
    dur_p[:n] = dur_s

    with TEL.launch("reduce", ("reduce", Np, Sb, nb), Np):
        calls, lsum, hist = _reduce_kernel(  # host scalar/slices: see above
            jnp.asarray(sid_p), jnp.asarray(dur_p), np.int32(n),
            jnp.asarray(np.asarray(bucket_edges, np.float32)), Sb, nb
        )
        out = (np.asarray(calls)[:n_series].astype(np.int64),
               np.asarray(lsum)[:n_series].astype(np.float64),
               np.asarray(hist)[:n_series].astype(np.int64))
    return out
