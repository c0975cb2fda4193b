"""Streaming metrics-generator (the PR-17 device reduction plane).

The load-bearing property is the DIFFERENTIAL: the streaming
processors (coded columns + packed-key series assembly + per-window
device folds) must be bit-identical to the legacy decoded-trace
processors across randomized push/cut/flush interleavings -- both
expose through the same registry/exposition code, so comparing
metrics_text() lines compares every counter, histogram bucket and
exemplar at once. Durations are dyadic (exact in float32) so "bit
identical" is a hard equality, not a tolerance.
"""

import random
import time

import numpy as np
import pytest

from tempo_tpu.ingest.columnar import LiveDict, span_columns_from_trace
from tempo_tpu.services.generator import (
    LATENCY_BUCKETS,
    MetricsGenerator,
    ServiceGraphsProcessor,
    SpanMetricsProcessor,
    StreamingServiceGraphs,
    StreamingSpanMetrics,
)
from tempo_tpu.services.overrides import Limits, Overrides
from tempo_tpu.wire.model import Resource, ResourceSpans, ScopeSpans, Span, Trace

TENANT = "t1"

# dyadic seconds: exact in f32 AND in the f64 accumulators, so host and
# device folds agree bit-for-bit regardless of summation order
_DYADIC_NS = (125_000_000, 250_000_000, 500_000_000, 1_000_000_000,
              62_500_000, 2_000_000_000)
_SERVICES = ["api-gateway", "auth", "cart", "db", "payments"]
_OPS = ["GET /", "POST /api", "db.query", "rpc.Call"]


def _span(rng, tid, svc_unused, name, kind, status, parent=b"", span_id=None):
    start = 1_700_000_000_000_000_000 + rng.randrange(10**9)
    dur = rng.choice(_DYADIC_NS)
    return Span(trace_id=tid, span_id=span_id or rng.getrandbits(64).to_bytes(8, "big"),
                parent_span_id=parent, name=name, kind=kind,
                start_unix_nano=start, end_unix_nano=start + dur,
                status_code=status)


def _graph_trace(rng):
    """One trace holding a client/server pair (sometimes unpaired,
    sometimes failed) plus internal spans: exercises series assembly,
    edge pairing, exemplars and the failed path together."""
    tid = rng.getrandbits(128).to_bytes(16, "big")
    tr = Trace()
    csvc, ssvc = rng.sample(_SERVICES, 2)
    cid = rng.getrandbits(64).to_bytes(8, "big")
    c_status = 2 if rng.random() < 0.2 else 0
    client = _span(rng, tid, csvc, "call " + rng.choice(_OPS), 3, c_status,
                   span_id=cid)
    tr.resource_spans.append(ResourceSpans(
        resource=Resource(attrs={"service.name": csvc}),
        scope_spans=[ScopeSpans(spans=[client])]))
    spans = []
    if rng.random() < 0.8:  # paired server half (else the edge dangles)
        spans.append(_span(rng, tid, ssvc, "serve " + rng.choice(_OPS), 2,
                           2 if rng.random() < 0.2 else 0, parent=cid))
    for _ in range(rng.randrange(0, 3)):
        spans.append(_span(rng, tid, ssvc, rng.choice(_OPS),
                           rng.choice([1, 4, 5]), 2 if rng.random() < 0.1 else 0))
    if spans:
        tr.resource_spans.append(ResourceSpans(
            resource=Resource(attrs={"service.name": ssvc}),
            scope_spans=[ScopeSpans(spans=spans)]))
    return tr


@pytest.mark.parametrize("seed", [3, 11, 29])
def test_streaming_matches_legacy_differential(seed):
    """Randomized interleavings of push / collect (the cut analog) /
    metrics_text (the flush/scrape analog): every exposition line --
    counters, bucket cumsums, exemplars, service-graph edges -- from
    the streaming plane equals the legacy decoded-trace plane."""
    rng = random.Random(seed)
    legacy_sm = SpanMetricsProcessor()
    legacy_sg = ServiceGraphsProcessor()
    stream = MetricsGenerator(Overrides(), stale_series_s=3600.0)

    for _ in range(rng.randrange(6, 12)):
        batch = [_graph_trace(rng) for _ in range(rng.randrange(1, 5))]
        legacy_sm.push(TENANT, batch)
        legacy_sg.push(TENANT, batch)
        stream.push(TENANT, batch)
        r = rng.random()
        if r < 0.3:  # mid-stream cut: legacy folds its buffered columns
            legacy_sm.collect()
            legacy_sg.collect()
        elif r < 0.5:  # mid-stream scrape on both planes
            legacy_sm.metrics_text()
            legacy_sg.metrics_text()
            stream.metrics_text()

    legacy = sorted(legacy_sm.metrics_text() + legacy_sg.metrics_text())
    streaming = sorted(stream.metrics_text())
    assert streaming == legacy
    assert any(l.startswith("traces_service_graph_request_total") for l in legacy)
    # unpaired edges match too (dangling client halves, not yet expired)
    sg = stream._procs(TENANT)["service-graphs"]
    assert len(sg.pending) == len(legacy_sg.pending)


def test_streaming_shed_matches_legacy_and_readmits():
    """max-active-series sheds the same spans on both planes, and a
    shed key is NOT cached: capacity freed by eviction re-admits it."""
    rng = random.Random(5)
    traces = [_graph_trace(rng) for _ in range(10)]
    legacy = SpanMetricsProcessor(max_active_series=3)
    legacy.push(TENANT, traces)
    ov = Overrides(defaults=Limits(metrics_generator_max_active_series=3))
    gen = MetricsGenerator(ov, stale_series_s=3600.0)
    gen.push(TENANT, traces)
    sm = gen._procs(TENANT)["span-metrics"]
    assert sm.dropped_series == legacy.dropped_series > 0
    assert sorted(sm.metrics_text()) == sorted(legacy.metrics_text())
    # evict everything -> the previously-shed keys can claim the freed
    # slots (the packed caches were cleared wholesale)
    assert sm.evict_stale(0.0) == 3
    n = sm.push_columns([span_columns_from_trace(traces[-1], LiveDict().code)],
                        LiveDict())
    assert n > 0 and len(sm.keys) <= 3


def test_edge_reduce_device_host_twin_parity():
    """edge_metrics_reduce: the fused device program, its host twin and
    a numpy oracle agree exactly on integer outputs and bit-for-bit on
    dyadic-duration sums."""
    from tempo_tpu.ops.reduce import _edge_reduce_host, edge_metrics_reduce

    rng = np.random.default_rng(7)
    n, e = 400, 13
    eid = rng.integers(0, e, size=n).astype(np.int32)
    cdur = (rng.integers(1, 64, size=n) * 0.125).astype(np.float32)
    sdur = (rng.integers(1, 64, size=n) * 0.0625).astype(np.float32)
    failed = (rng.random(n) < 0.3).astype(np.int32)
    dev = edge_metrics_reduce(eid, cdur, sdur, failed, e, LATENCY_BUCKETS)
    host = _edge_reduce_host(eid, cdur, sdur, failed, e, LATENCY_BUCKETS)
    for d, h in zip(dev, host):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(h))
    edges_f32 = np.asarray(LATENCY_BUCKETS, np.float32)
    for k in range(e):
        m = eid == k
        assert dev[0][k] == m.sum()
        assert dev[1][k] == failed[m].sum()
        assert dev[2][k] == cdur[m].astype(np.float64).sum()
        assert dev[3][k] == sdur[m].astype(np.float64).sum()
        np.testing.assert_array_equal(
            dev[4][k], np.bincount(np.searchsorted(edges_f32, cdur[m]),
                                   minlength=len(LATENCY_BUCKETS) + 1))
    # empty window short-circuits with correctly-shaped zeros
    z = edge_metrics_reduce(np.zeros(0, np.int32), np.zeros(0, np.float32),
                            np.zeros(0, np.float32), np.zeros(0, np.int32),
                            e, LATENCY_BUCKETS)
    assert all(np.asarray(a).sum() == 0 for a in z)


def test_tap_zero_extra_decodes(tmp_path):
    """The counter proof for the tentpole claim: the streaming tap reads
    SpanColumns out of ColumnarIngest's identity-keyed cache, so after a
    push window + tap drain the decode counter equals the cached-segment
    count -- zero proto walks beyond the one ingest decode."""
    from tempo_tpu.services.app import App, AppConfig
    from tempo_tpu.services.ingester import IngesterConfig
    from tempo_tpu.util.testdata import make_traces
    from tempo_tpu.wire.otlp_pb import encode_trace

    app = App(AppConfig(
        target="all", storage_path=str(tmp_path / "store"),
        compaction_cycle_s=9999,
        ingester=IngesterConfig(max_trace_idle_s=0.0, max_block_age_s=0.0,
                                flush_check_period_s=9999)))
    app.start()
    try:
        traces = make_traces(8, seed=13, n_spans=4)
        for _, tr in traces:
            app.distributor.push_raw(TENANT, encode_trace(tr))
        app.distributor.flush_generator_tap()
        st = app.ingester.instance(TENANT).columnar.stats()
        assert st["decodes"] > 0
        assert st["decodes"] - st["cached"] == 0, st
        # and the window actually became series
        lines = app.generator.metrics_text()
        calls = [l for l in lines
                 if l.startswith("traces_spanmetrics_calls_total")]
        total = sum(int(l.rsplit(" ", 1)[1]) for l in calls)
        assert total == sum(t.span_count() for _, t in traces)
    finally:
        app.stop()


def test_generator_off_read_path_unchanged(tmp_path):
    """enable_generator=False: no tap, no generator, and the read path
    serves pushes exactly as before."""
    from tempo_tpu.services.app import App, AppConfig
    from tempo_tpu.services.ingester import IngesterConfig
    from tempo_tpu.util.testdata import make_traces

    app = App(AppConfig(
        target="all", storage_path=str(tmp_path / "store"),
        enable_generator=False, compaction_cycle_s=9999,
        ingester=IngesterConfig(max_trace_idle_s=0.0, max_block_age_s=0.0,
                                flush_check_period_s=9999)))
    app.start()
    try:
        assert app.generator is None
        assert app.distributor.generator_window is None
        assert app.distributor.generator_forward is None
        traces = make_traces(5, seed=3, n_spans=3)
        for _, tr in traces:
            app.distributor.push(TENANT, tr.resource_spans)
        app.distributor.flush_generator_tap()
        for tid, tr in traces:
            got = app.querier.find_trace_by_id(TENANT, tid)
            assert got is not None and got.span_count() == tr.span_count()
    finally:
        app.stop()


def test_kerneltel_generator_plane():
    """The generator section of /status/kernels: windows, edge-store
    depth, per-stage time, shed counters and the freshness aggregate."""
    from tempo_tpu.util.kerneltel import TEL

    g0 = TEL.generator_stats()
    with TEL.stage("generator:span-metrics"):
        pass
    TEL.record_generator_window(40, 7, unpaired=3, expired=1)
    TEL.record_generator_shed(TENANT, 2)
    TEL.record_generator_freshness(0.25)
    g = TEL.generator_stats()
    assert g["windows"] == g0["windows"] + 1
    assert g["window_spans"] == g0["window_spans"] + 40
    assert g["edges_completed"] == g0["edges_completed"] + 7
    assert g["unpaired"] == 3 and g["expired"] == 1
    assert g["shed"].get(TENANT, 0) >= 2
    assert g["stages"]["span-metrics"]["count"] >= 1
    assert g["freshness_max_s"] >= 0.25 and g["freshness_avg_s"] > 0
    assert "generator" in TEL.snapshot()


def test_generator_freshness_slo_objective():
    """Targets hosting a generator carry the push->series-visible
    freshness objective; generator-less targets don't."""
    from tempo_tpu.services.app import build_default_slo

    gen = MetricsGenerator(Overrides())
    names = [o.name for o in build_default_slo(None, gen).objectives()]
    assert names == ["generator-freshness"]
    assert "generator-freshness" not in [
        o.name for o in build_default_slo(None, None).objectives()]


def test_streaming_exemplars_carry_trace_ids():
    """Exemplar plumbing end to end: the last trace to touch a series
    is the one its bucket exemplar names."""
    rng = random.Random(19)
    gen = MetricsGenerator(Overrides(), stale_series_s=3600.0)
    tr = _graph_trace(rng)
    gen.push(TENANT, [tr])
    tid_hex = tr.resource_spans[0].scope_spans[0].spans[0].trace_id.hex()
    text = "\n".join(gen.metrics_text())
    assert f'trace_id="{tid_hex}"' in text
