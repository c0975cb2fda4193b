"""Batched trace-ID lookup kernel.

Replaces the reference's per-block bloom -> index binary search -> page
scan (vparquet/block_findtracebyid.go:56-203) with one vectorized
device binary search: Q query ids against a block's sorted 128-bit
trace-id index, ids as 4 order-preserving int32 lanes
(schema.trace_id_to_codes). All Q queries step through the log2(T)
bisection together as one (Q,4) vs (T,4) lexicographic compare per
step -- the shape the VPU wants, and the unit the sharded multi-chip
Find distributes (parallel/find.py).
"""

from __future__ import annotations

import time as _time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..util.kerneltel import TEL
from .device import PAD_I32, bucket, pad_rows, scoped


def _lex_less(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Rowwise a < b for (..., 4) int32 lanes, lexicographic."""
    lt = a < b
    eq = a == b
    return lt[..., 0] | (
        eq[..., 0] & (lt[..., 1] | (eq[..., 1] & (lt[..., 2] | (eq[..., 2] & lt[..., 3]))))
    )


def _lex_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(a == b, axis=-1)


def bisect_ids(ids: jnp.ndarray, queries: jnp.ndarray, n_valid, n_steps: int) -> jnp.ndarray:
    """Core lockstep bisection (unjitted; shared with parallel/find.py).
    ids: (T,4) sorted i32 codes (padded with +max rows), queries: (Q,4),
    n_valid: () number of real id rows. -> (Q,) int32 sid or -1."""
    T = ids.shape[0]
    Q = queries.shape[0]
    lo = jnp.zeros((Q,), dtype=jnp.int32)
    hi = jnp.full((Q,), n_valid, dtype=jnp.int32)

    def step(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        mid_ids = ids[jnp.clip(mid, 0, T - 1)]
        less = _lex_less(mid_ids, queries)
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(less, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n_steps, step, (lo, hi))
    found_ids = ids[jnp.clip(lo, 0, T - 1)]
    ok = (lo < n_valid) & _lex_eq(found_ids, queries)
    return jnp.where(ok, lo, -1)


@partial(jax.jit, static_argnames=("n_steps",))
@scoped("find")
def _lookup_kernel(ids: jnp.ndarray, queries: jnp.ndarray, n_valid: jnp.ndarray, n_steps: int):
    return bisect_ids(ids, queries, n_valid, n_steps)


@partial(jax.jit, static_argnames=("n_steps",))
@scoped("find")
def _lookup_blocks_kernel(ids: jnp.ndarray, queries: jnp.ndarray, n_valid: jnp.ndarray,
                          n_steps: int):
    """ids: (B, T, 4) stacked per-block indexes -> (B, Q) sids. One fused
    program bisects every candidate block at once: the single-chip unit
    of the multi-block Find (parallel/find.py shards the B axis)."""
    return jax.vmap(lambda a, nv: bisect_ids(a, queries, nv, n_steps))(ids, n_valid)


def _device_ids(blk) -> tuple[jnp.ndarray, int]:
    """Padded (T,4) device copy of a block's sorted id codes, cached on
    the (immutable) block object: repeated finds skip the host->device
    upload, which would otherwise dominate per-lookup latency."""
    cached = getattr(blk, "_dev_ids", None)
    a = blk.trace_index["trace.id_codes"]
    n = int(a.shape[0])
    if cached is not None and cached[1] == n:
        return cached
    tb = bucket(max(n, 1))
    ids = pad_rows(np.asarray(a, dtype=np.int32), tb, np.int32(2**31 - 1))
    cached = (jnp.asarray(ids), n)
    blk._dev_ids = cached
    return cached


def _ids_void(blk) -> np.ndarray:
    """The block's sorted trace ids as a void16 view (numpy compares V16
    lexicographically by bytes = the on-disk sort order), cached on the
    immutable block."""
    v = getattr(blk, "_ids_void_cache", None)
    if v is None:
        v = blk._ids_void_cache = np.ascontiguousarray(
            blk.trace_index["trace.id"]).view("V16").ravel()
    return v


def lookup_ids_blocks_host(blocks: list, query_codes: np.ndarray) -> np.ndarray:
    """Host engine: ONE vectorized searchsorted per block over the void16
    id index. O(Q log T) with zero device round trips -- on a single
    chip this beats the kernel by the full dispatch+fetch RTT; the device
    kernel's value is mesh sharding (parallel/find.py) and fused
    multi-block batches at scale."""
    B, q = len(blocks), query_codes.shape[0]
    out = np.full((B, q), -1, dtype=np.int32)
    if B == 0 or q == 0:
        return out
    from ..block.schema import codes_to_id_bytes

    qbytes = np.ascontiguousarray(codes_to_id_bytes(np.asarray(query_codes, np.int32)))
    qv = qbytes.view("V16").ravel()
    from ..native import lex_bisect16

    for i, blk in enumerate(blocks):
        iv = _ids_void(blk)
        n = iv.shape[0]
        if n == 0:
            continue
        # native memcmp bisect (~10x numpy's void16 searchsorted, whose
        # per-probe compares go through object machinery)
        rows = lex_bisect16(iv.view(np.uint8).reshape(n, 16), qbytes)
        if rows is not None:
            out[i] = rows
            continue
        pos = np.searchsorted(iv, qv)
        clip = np.minimum(pos, n - 1)
        ok = (pos < n) & (iv[clip] == qv)
        out[i, ok] = pos[ok].astype(np.int32)
    return out


def _lookup_blocks_device(blocks: list, query_codes: np.ndarray) -> np.ndarray:
    """The device engine body: per-block cached device id indexes, one
    lockstep bisection kernel per id-row bucket, one timing window over
    the whole batch. Shared by the routed entry below and the
    calibration race."""
    q = query_codes.shape[0]
    qb = bucket(q)
    # host arrays ride the dispatch upload; eager jnp conversions here
    # would each pay a blocking host->device round trip
    queries = pad_rows(np.asarray(query_codes, np.int32), qb, PAD_I32)
    outs = []
    t0 = _time.perf_counter()
    buckets = []
    for blk in blocks:
        dev_ids, n = _device_ids(blk)
        tb = int(dev_ids.shape[0])  # id-row bucket: the launch key's label
        n_steps = tb.bit_length()
        nv = np.int32(n)
        TEL.record_launch(
            "find", ("find1", tb, qb), tb,
            cost=lambda dev_ids=dev_ids, nv=nv, n_steps=n_steps: _costmodel(
            ).spec(_lookup_kernel, dev_ids, queries, nv, n_steps))
        buckets.append(tb)
        outs.append(_lookup_kernel(dev_ids, queries, nv, n_steps))
    stacked = jnp.stack(outs) if len(outs) > 1 else outs[0][None]
    res = np.asarray(stacked)[:, :q]
    # one timing window covers the whole batch (per-block syncs would
    # serialize the pipeline): the histogram gets one observation, each
    # launched bucket's kernel row an amortized share
    dt = _time.perf_counter() - t0
    TEL.device_time.observe(dt, 'op="find"')
    for tb in buckets:
        TEL.credit_device("find", tb, dt / len(buckets))
    return res


def _costmodel():
    from ..util import costmodel

    return costmodel


def _n_devices() -> int:
    """Visible chip count (own function so topology tests can pin it)."""
    return len(jax.devices())


def _find_policy(mode: str, rows: int) -> tuple[str, str]:
    """Resolve the find engine for a SINGLE-chip topology:
    (engine, routing reason). TEMPO_FIND_MODE overrides the caller's
    mode (env always wins); 'auto' consults the CostLedger's measured
    find race (tempo-tpu-cli calibrate / the find_auto_crossover_rows
    bench row): host cost is linear in scanned id rows while the device
    path is ~fixed, so THIS batch's row count is compared against the
    committed crossover_rows -- a race calibrated on a small block
    still routes a huge multi-block lookup to the device once it is
    past the crossover. Entries without crossover_rows fall back to
    the race's binary winner; no entry at all falls back to the
    host-on-one-chip assumption."""
    import os

    env = os.environ.get("TEMPO_FIND_MODE", "")
    if env in ("host", "device", "auto"):
        mode = env
    if mode == "host":
        return "host", "forced"
    if mode == "device":
        return "device", "forced"
    from ..util.costledger import KEY_FIND, ledger

    entry = ledger().get(KEY_FIND)
    if entry:
        cross = entry.get("crossover_rows")
        if cross and float(cross) > 0:
            return (("device" if rows >= float(cross) else "host"),
                    "ledger_crossover")
        if entry.get("winner") in ("host", "device"):
            return entry["winner"], "ledger_crossover"
    return "host", "single_chip_rtt"


def lookup_ids_blocks_cached(blocks: list, query_codes: np.ndarray,
                             mode: str = "auto") -> np.ndarray:
    """Batched multi-block lookup, engine picked per topology +
    measured crossover. A mesh of chips always runs the device kernel
    (ids stay device-resident and shard over the mesh); on a single
    chip 'auto' routes by the CostLedger's committed host-vs-device
    race (_find_policy) -- the host searchsorted engine remains the
    default only until someone actually measures. Both engines return
    bit-identical (B, Q) int32 row-in-block (-1 miss)."""
    B = len(blocks)
    q = query_codes.shape[0]
    if B == 0 or q == 0:
        return np.full((B, q), -1, dtype=np.int32)
    if mode != "host" and _n_devices() > 1:
        TEL.record_routing("find", "device",
                           "forced" if mode == "device" else "mesh")
        return _lookup_blocks_device(blocks, query_codes)
    # id-index rows of THIS batch, from footer metadata (no IO)
    rows = sum(int(b.meta.total_traces) for b in blocks)
    engine, reason = _find_policy(mode, rows)
    TEL.record_routing("find", engine, reason)
    if engine == "host":
        return lookup_ids_blocks_host(blocks, query_codes)
    return _lookup_blocks_device(blocks, query_codes)


def calibrate_find(blocks: list, query_codes: np.ndarray, repeats: int = 3,
                   record: bool = True) -> dict:
    """THE find race (ROADMAP item 5): run both engines over the same
    blocks/queries, take best-of-repeats (noise only ever adds time),
    and commit the measured crossover to the CostLedger so the `auto`
    policy stops guessing. Returns the ledger entry.

    crossover_rows models the host engine as linear in scanned id rows
    and the device engine as a ~fixed dispatch+fetch: the id-row count
    at which the device path starts winning for this query batch."""
    rows = int(sum(b.trace_index["trace.id_codes"].shape[0] for b in blocks))
    q = int(query_codes.shape[0])

    def best(fn) -> float:
        fn()  # warm: device compiles + id uploads; host void16 caches
        times = []
        for _ in range(max(1, repeats)):
            t0 = _time.perf_counter()
            fn()
            times.append(_time.perf_counter() - t0)
        return min(times)

    host_s = best(lambda: lookup_ids_blocks_host(blocks, query_codes))
    device_s = best(lambda: _lookup_blocks_device(blocks, query_codes))
    host_per_row = host_s / max(rows, 1)
    entry = {
        "host_s": round(host_s, 6),
        "device_s": round(device_s, 6),
        "host_s_per_row": host_per_row,
        "rows": rows,
        "queries": q,
        "repeats": int(repeats),
        "winner": "host" if host_s <= device_s else "device",
        "crossover_rows": round(device_s / max(host_per_row, 1e-12), 1),
    }
    if record:
        from ..util.costledger import KEY_FIND, ledger

        ledger().update(KEY_FIND, **entry)
        ledger().publish()
    return entry


def lookup_ids_blocks(id_code_arrays: list[np.ndarray], query_codes: np.ndarray) -> np.ndarray:
    """Batched multi-block lookup on one chip: Q query ids against B
    per-block sorted id-code arrays. Returns (B, Q) int32 row-in-block
    (-1 miss). Every block reporting its own hit row (rather than electing
    one winner) is what lets callers combine partial traces, matching the
    reference's Find fan-out + combiner (tempodb/tempodb.go:271-352)."""
    B = len(id_code_arrays)
    q = query_codes.shape[0]
    if B == 0 or q == 0:
        return np.full((B, q), -1, dtype=np.int32)
    T = bucket(max(max(a.shape[0] for a in id_code_arrays), 1))
    ids = np.full((B, T, 4), np.int32(2**31 - 1), dtype=np.int32)
    n_valid = np.zeros((B,), dtype=np.int32)
    for i, a in enumerate(id_code_arrays):
        ids[i, : a.shape[0]] = a
        n_valid[i] = a.shape[0]
    qb = bucket(q)
    queries = pad_rows(np.asarray(query_codes, dtype=np.int32), qb, PAD_I32)
    n_steps = int(T).bit_length()
    with TEL.launch(
        "find", ("findB", B, T, qb), T,
        cost=lambda: _costmodel().spec(
            _lookup_blocks_kernel, ids, queries, n_valid, n_steps)):
        out = _lookup_blocks_kernel(ids, queries, n_valid, n_steps)
        res = np.asarray(out)[:, :q]
    return res


def lookup_ids(id_codes: np.ndarray, query_codes: np.ndarray) -> np.ndarray:
    """Host wrapper: pad to buckets, run the kernel, return (Q,) sids (-1 miss)."""
    n = id_codes.shape[0]
    q = query_codes.shape[0]
    if n == 0 or q == 0:
        return np.full((q,), -1, dtype=np.int32)
    tb = bucket(n)
    qb = bucket(q)
    # pad ids with +inf rows (max codes) so they sort after everything
    ids = pad_rows(np.asarray(id_codes, dtype=np.int32), tb, np.int32(2**31 - 1))
    queries = pad_rows(np.asarray(query_codes, dtype=np.int32), qb, PAD_I32)
    n_steps = int(tb).bit_length()  # ceil(log2(tb)) + 1 covers the range
    nv = np.int32(n)
    with TEL.launch(
        "find", ("find1", tb, qb), tb,
        cost=lambda: _costmodel().spec(_lookup_kernel, ids, queries, nv, n_steps)):
        out = _lookup_kernel(ids, queries, nv, n_steps)
        res = np.asarray(out)[:q]
    return res
