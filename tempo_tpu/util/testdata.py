"""Random OTLP trace generation and synthetic block construction for
tests, benchmarks and the chip smoke.

Role parity with the reference's pkg/util/test MakeTrace helpers used
throughout its test suite (SURVEY.md section 4.4). Deterministic given a
seed so golden tests are stable.
"""

from __future__ import annotations

import random

import numpy as np

from ..wire.model import Event, Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace

_SERVICES = ["api-gateway", "auth", "cart", "checkout", "db", "frontend", "payments", "search"]
_OPS = ["GET /", "GET /api", "POST /api/orders", "db.query", "cache.get", "rpc.Call", "render"]
_HTTP_METHODS = ["GET", "POST", "PUT", "DELETE"]


def make_trace_id(rng: random.Random) -> bytes:
    return rng.getrandbits(128).to_bytes(16, "big")


def make_span_id(rng: random.Random) -> bytes:
    return rng.getrandbits(64).to_bytes(8, "big")


def make_trace(
    rng: random.Random | int = 0,
    trace_id: bytes | None = None,
    n_spans: int = 8,
    base_time_ns: int = 1_700_000_000_000_000_000,
    n_batches: int = 2,
) -> Trace:
    if isinstance(rng, int):
        rng = random.Random(rng)
    tid = trace_id or make_trace_id(rng)
    t = Trace()
    span_ids: list[bytes] = []
    per_batch = max(1, n_spans // max(1, n_batches))
    remaining = n_spans
    while remaining > 0:
        n = min(per_batch, remaining)
        remaining -= n
        svc = rng.choice(_SERVICES)
        rs = ResourceSpans(
            resource=Resource(
                attrs={
                    "service.name": svc,
                    "k8s.cluster.name": "prod",
                    "k8s.namespace.name": rng.choice(["default", "apps"]),
                }
            )
        )
        ss = ScopeSpans(scope=Scope(name="test-scope", version="1"))
        for _ in range(n):
            start = base_time_ns + rng.randrange(0, 10**9)
            dur = rng.randrange(10_000, 2 * 10**9)
            sid = make_span_id(rng)
            sp = Span(
                trace_id=tid,
                span_id=sid,
                parent_span_id=rng.choice(span_ids) if span_ids and rng.random() < 0.7 else b"",
                name=rng.choice(_OPS),
                kind=rng.randrange(1, 6),
                start_unix_nano=start,
                end_unix_nano=start + dur,
                status_code=2 if rng.random() < 0.1 else 0,
                attrs={
                    "http.method": rng.choice(_HTTP_METHODS),
                    "http.status_code": rng.choice([200, 200, 200, 404, 500]),
                    "component": rng.choice(["net/http", "grpc", "sql"]),
                    "latency.weight": rng.random(),
                    "cache.hit": rng.random() < 0.5,
                },
            )
            if rng.random() < 0.3:
                sp.events.append(
                    Event(time_unix_nano=start + dur // 2, name="exception", attrs={"exception.type": "IOError"})
                )
            span_ids.append(sid)
            ss.spans.append(sp)
        rs.scope_spans.append(ss)
        t.resource_spans.append(rs)
    return t


def make_traces(
    n: int, seed: int = 0, n_spans: int = 8,
    base_time_ns: int = 1_700_000_000_000_000_000,
) -> list[tuple[bytes, Trace]]:
    """n distinct traces, sorted by trace id (block-build friendly)."""
    rng = random.Random(seed)
    out = []
    seen = set()
    while len(out) < n:
        tid = make_trace_id(rng)
        if tid in seen:
            continue
        seen.add(tid)
        out.append((tid, make_trace(rng, trace_id=tid, n_spans=n_spans, base_time_ns=base_time_ns)))
    out.sort(key=lambda p: p[0])
    return out


def restart_trace(trace: Trace, start_ns: int) -> Trace:
    """Shift every span (and event) of `trace` in place so that its
    earliest span starts at exactly `start_ns`: make_trace draws every
    start inside one second, and a time-window test needs traces at
    chosen nanoseconds around the window's edges."""
    shift = start_ns - trace.time_range_nanos()[0]
    for _, _, sp in trace.all_spans():
        sp.start_unix_nano += shift
        sp.end_unix_nano += shift
        for ev in sp.events:
            ev.time_unix_nano += shift
    return trace


# ------------------------------------------------------------ synth block
SYNTH_BASE_TIME_NS = 1_700_000_000_000_000_000


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
                  base_time_ns: int = SYNTH_BASE_TIME_NS):
    """Fast numpy construction of a realistic vtpu block's columns (same
    column set the builder emits; conformance-tested in
    tests/test_bench_synth.py): 100 attribute keys, 5,000 values, 64
    services, 512 span names, span starts inside the hour after
    base_time_ns. -> (cols, strings, ids): the column dict, the sorted
    dictionary strings (a string's code is its index) and the sorted
    (n_traces, 16) uint8 trace ids. Needs no jax."""
    from ..block import schema as S
    from ..block.builder import build_tres

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


def write_synth_block(backend, tenant: str, cols: dict, strings: list[str],
                      ids: np.ndarray):
    """Write synth_columns' output as one block -> its BlockMeta."""
    from ..block import schema as S
    from ..block.bloom import ShardedBloom
    from ..block.builder import FinalizedBlock, compute_row_groups, write_block
    from ..block.dictionary import Dictionary
    from ..block.meta import BlockMeta

    n_traces, n_spans = ids.shape[0], cols["span.trace_sid"].shape[0]
    axes, col_axis, row_groups = compute_row_groups(
        cols, cols["span.start_ms"], cols["span.dur_us"], S.DEFAULT_ROW_GROUP_SPANS
    )
    m = BlockMeta.new(tenant)
    m.total_traces, m.total_spans = n_traces, n_spans
    m.min_id, m.max_id = ids[0].tobytes().hex(), ids[-1].tobytes().hex()
    m.start_time_unix_nano = int(cols["span.start_ns"].min())
    m.end_time_unix_nano = int(cols["span.end_ns"].max())
    m.dict_size = len(strings)
    m.row_groups = row_groups
    bloom = ShardedBloom.for_estimated_items(n_traces)
    bloom.add_many([ids[i].tobytes() for i in range(n_traces)])
    m.bloom_shards, m.bloom_shard_bits = bloom.n_shards, bloom.shard_bits
    fin = FinalizedBlock(m, cols, axes, col_axis, Dictionary(strings), bloom)
    return write_block(backend, fin)


def synth_block(backend, tenant: str, rng: np.random.Generator, n_traces: int,
                spans_per: int, n_res: int = 1024, attrs_per_span: int = 2,
                base_time_ns: int = SYNTH_BASE_TIME_NS):
    """synth_columns + write_synth_block -> (BlockMeta, ids). Benchmarks
    and the chip smoke measure the READ side; wire-object building would
    only measure Python."""
    cols, strings, ids = synth_columns(rng, n_traces, spans_per, n_res,
                                       attrs_per_span, base_time_ns)
    return write_synth_block(backend, tenant, cols, strings, ids), ids
