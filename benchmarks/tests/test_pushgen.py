from benchmarks.lib.pushgen import PushLog, PushTemplate


def test_patched_body_decodes_to_the_expected_traces():
    from tempo_tpu.wire.otlp_pb import decode_trace

    pt = PushTemplate(seed=4, traces=4, spans=6, traces_per_bucket=2)
    base = 1_790_000_000_000_000_000
    a, b = pt.body(11, base), pt.body(12, base)
    assert len(a) == len(b) == pt.nbytes and a != b
    assert pt.body(11, base) == a  # the same seed and index: the same bytes
    tr = decode_trace(a)
    assert len(tr.resource_spans) == 4
    for t, rs in enumerate(tr.resource_spans):
        spans = rs.scope_spans[0].spans
        assert {s.trace_id.hex() for s in spans} == {pt.trace_id(11, t)}
        got = {(s.span_id.hex(), s.name, s.start_unix_nano, s.end_unix_nano)
               for s in spans}
        assert got == pt.expected_spans(11, t, base)
        assert spans[1].parent_span_id == spans[0].span_id
        assert {s.attrs["smoke.bucket"] for s in spans} == {pt.bucket_of(11, t)}
    assert pt.bucket_of(11, 0) == pt.bucket_of(11, 1) != pt.bucket_of(11, 2)
    assert pt.bucket_members(11, pt.bucket_of(11, 3)) == {pt.trace_id(11, 2), pt.trace_id(11, 3)}


def test_push_log_hands_out_only_old_enough_acks():
    log = PushLog()
    log.add(1, 10)
    assert log.older_than(5.0) == []
    assert [a[0] for a in log.older_than(0.0)] == [1]
