"""The benchmark's own copy of the column generator.

Copied from tempo_tpu/util/testdata.py (`synth_columns`, `_trace_local_res`)
so that a later PR to the program cannot change the corpus the benchmark
measures on. benchmarks/tests/test_synth.py holds it byte-equal to the
original at a tiny size for as long as the original exists. Two things stay
imports because they are the block FORMAT and not the yardstick: the list
of well-known resource columns and `build_tres` (the per-trace resource
index the block carries). Needs no jax.
"""

from __future__ import annotations

import numpy as np


def _trace_local_res(rng: np.random.Generator, n_traces: int, spans_per: int,
                     n_res: int) -> np.ndarray:
    """Per-span resource indices with per-trace locality: each trace
    draws 2-4 resources and its spans choose among them."""
    k = 4  # palette size per trace (first 2 always used, rest maybe)
    palette = rng.integers(0, n_res, size=(n_traces, k))
    pick = rng.integers(0, k, size=(n_traces, spans_per))
    pick = np.minimum(pick, rng.integers(1, k, size=(n_traces, 1)))
    return np.take_along_axis(palette, pick, axis=1).reshape(-1).astype(np.int32)


def synth_columns(rng: np.random.Generator, n_traces: int, spans_per: int,
                  n_res: int = 1024, attrs_per_span: int = 2,
                  base_time_ns: int = 0):
    """Fast numpy construction of a realistic vtpu block's columns (same
    column set the builder emits; conformance-tested in
    tests/test_bench_synth.py): 100 attribute keys, 5,000 values, 64
    services, 512 span names, span starts inside the hour after
    base_time_ns. -> (cols, strings, ids): the column dict, the sorted
    dictionary strings (a string's code is its index) and the sorted
    (n_traces, 16) uint8 trace ids. Needs no jax."""
    from tempo_tpu.block import schema as S
    from tempo_tpu.block.builder import build_tres

    keys = [f"attr.key{i:03d}" for i in range(100)]
    vals = [f"value-{i:05d}" for i in range(5000)]
    svcs = [f"svc-{i:03d}" for i in range(64)]
    ops = [f"op-{i:04d}" for i in range(512)]
    strings = sorted({"", *keys, *vals, *svcs, *ops})
    code = {s: i for i, s in enumerate(strings)}
    codes_of = lambda lst: np.asarray([code[s] for s in lst], np.int32)  # noqa: E731
    key_codes, val_codes = codes_of(keys), codes_of(vals)
    svc_codes, op_codes = codes_of(svcs), codes_of(ops)

    n_spans = n_traces * spans_per
    ids = rng.integers(0, 256, size=(n_traces, 16), dtype=np.uint8)
    u = ids.view(">u8").astype(np.uint64).reshape(n_traces, 2)
    order = np.lexsort((u[:, 1], u[:, 0]))
    ids = np.ascontiguousarray(ids[order])
    id_codes = (ids.view(">u4").astype(np.int64) - 0x80000000).astype(np.int32).reshape(n_traces, 4)

    span_off = (np.arange(n_traces + 1, dtype=np.int64) * spans_per).astype(np.int32)
    start_ns = (base_time_ns + rng.integers(0, 3_600_000_000_000, size=n_spans)).astype(np.uint64)
    dur_us = rng.integers(10, 1_000_000, size=n_spans).astype(np.int32)
    end_ns = (start_ns.astype(np.int64) + dur_us.astype(np.int64) * 1_000).astype(np.uint64)
    tmin = np.minimum.reduceat(start_ns.astype(np.int64), span_off[:-1])
    tmax = np.maximum.reduceat(end_ns.astype(np.int64), span_off[:-1])
    blk_base = int(start_ns.min())

    span_ids = rng.integers(0, 256, size=(n_spans, 8), dtype=np.uint8)
    sat_owner = np.repeat(np.arange(n_spans, dtype=np.int32), attrs_per_span)
    n_sat = sat_owner.shape[0]
    # attribute keys are unique within a span, as OTLP requires (engines
    # may disagree on a span that repeats a key): attribute j takes key
    # (first + j * step) mod 100, step coprime to 100
    steps = np.asarray([x for x in range(1, len(keys))
                        if np.gcd(x, len(keys)) == 1])
    sat_key = (rng.integers(0, len(keys), size=(n_spans, 1))
               + rng.choice(steps, size=(n_spans, 1))
               * np.arange(attrs_per_span)[None, :]) % len(keys)
    e_i32 = np.empty(0, np.int32)

    cols = {
        "span.trace_sid": np.repeat(np.arange(n_traces, dtype=np.int32), spans_per),
        "span.name_id": rng.choice(op_codes, size=n_spans).astype(np.int32),
        "span.service_id": np.full(n_spans, -1, np.int32),
        "span.kind": rng.integers(1, 6, size=n_spans).astype(np.int32),
        "span.status": (rng.random(n_spans) < 0.05).astype(np.int32) * 2,
        "span.start_ms": ((start_ns.astype(np.int64) - blk_base) // 1_000_000).astype(np.int32),
        "span.dur_us": dur_us,
        "span.dur_lo": np.zeros(n_spans, np.int32),
        "span.http_status": rng.choice(np.asarray([200, 200, 200, 404, 500], np.int32), size=n_spans),
        "span.http_method_id": np.full(n_spans, -1, np.int32),
        "span.http_url_id": np.full(n_spans, -1, np.int32),
        # realistic resource locality: a trace's spans come from a
        # handful of services (2-4 resources per trace), the shape the
        # reference's nested ResourceSpans model assumes -- NOT one
        # random resource per span, which no tracing workload produces
        "span.res_idx": _trace_local_res(rng, n_traces, spans_per, n_res),
        "span.start_ns": start_ns,
        "span.end_ns": end_ns,
        "span.id": span_ids,
        # simple chain topology: span k's parent is span k-1 of the same
        # trace (first span is the root) -- gives structural queries a
        # real tree to walk; parent_id bytes mirror parent_idx so host
        # verification over materialized traces agrees with the device
        "span.parent_id": np.where(
            (np.arange(n_spans) % spans_per == 0)[:, None],
            np.zeros((1, 8), np.uint8), np.roll(span_ids, 1, axis=0)),
        "span.parent_idx": np.where(
            np.arange(n_spans, dtype=np.int32) % spans_per == 0,
            np.int32(-1), np.arange(n_spans, dtype=np.int32) - 1),
        "span.trace_state_id": np.zeros(n_spans, np.int32),
        "span.status_msg_id": np.zeros(n_spans, np.int32),
        "span.dropped_attrs": np.zeros(n_spans, np.int32),
        "span.scope_idx": np.zeros(n_spans, np.int32),
        "trace.id": ids,
        "trace.id_codes": id_codes,
        "trace.span_off": span_off,
        "trace.start_ms": ((tmin - blk_base) // 1_000_000).astype(np.int32),
        "trace.end_ms": ((tmax - blk_base) // 1_000_000).astype(np.int32),
        "trace.dur_us": np.clip((tmax - tmin) // 1_000, 0, 2**31 - 1).astype(np.int32),
        "trace.dur_lo": np.zeros(n_traces, np.int32),
        "trace.root_service_id": rng.choice(svc_codes, size=n_traces).astype(np.int32),
        "trace.root_name_id": rng.choice(op_codes, size=n_traces).astype(np.int32),
        "trace.start_ns": tmin.astype(np.uint64),
        "trace.end_ns": tmax.astype(np.uint64),
        "scope.name_id": np.zeros(1, np.int32),
        "scope.version_id": np.zeros(1, np.int32),
        "ev.span": e_i32, "ev.time_ns": np.empty(0, np.uint64),
        "ev.name_id": e_i32, "ev.dropped": e_i32,
        "ln.span": e_i32, "ln.trace_id": np.empty((0, 16), np.uint8),
        "ln.span_id": np.empty((0, 8), np.uint8), "ln.state_id": e_i32,
        **{f"{p}.{f}": np.empty(0, dt)
           for p, owner in (("evattr", "ev"), ("lnattr", "ln"))
           for f, dt in ((owner, np.int32), ("key_id", np.int32), ("vtype", np.int32),
                         ("str_id", np.int32), ("int32", np.int32), ("f32", np.float32),
                         ("int64", np.int64), ("f64", np.float64))},
        "sattr.span": sat_owner,
        "sattr.key_id": key_codes[sat_key.reshape(-1)],
        "sattr.vtype": np.zeros(n_sat, np.int32),
        "sattr.str_id": rng.choice(val_codes, size=n_sat).astype(np.int32),
        "sattr.int32": np.zeros(n_sat, np.int32),
        "sattr.f32": np.zeros(n_sat, np.float32),
        "sattr.int64": np.zeros(n_sat, np.int64),
        "sattr.f64": np.zeros(n_sat, np.float64),
        "rattr.res": np.arange(n_res, dtype=np.int32),
        "rattr.key_id": np.full(n_res, key_codes[0], np.int32),
        "rattr.vtype": np.zeros(n_res, np.int32),
        "rattr.str_id": rng.choice(val_codes, size=n_res).astype(np.int32),
        "rattr.int32": np.zeros(n_res, np.int32),
        "rattr.f32": np.zeros(n_res, np.float32),
        "rattr.int64": np.zeros(n_res, np.int64),
        "rattr.f64": np.zeros(n_res, np.float64),
    }
    for col in sorted(set(S.WELL_KNOWN_RES_ATTRS.values())):
        if col == "res.service_id":
            cols[col] = rng.choice(svc_codes, size=n_res).astype(np.int32)
        else:
            cols[col] = np.full(n_res, -1, np.int32)
    cols.update(build_tres(cols["span.trace_sid"], cols["span.res_idx"], n_traces))
    return cols, strings, ids
