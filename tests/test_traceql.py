"""TraceQL: parser unit tests + end-to-end execution against blocks."""

import pytest

from tempo_tpu.backend import MemBackend
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.db.search import SearchRequest
from tempo_tpu.traceql import ParseError, parse
from tempo_tpu.traceql.ast import Comparison, LogicalExpr, Scope
from tempo_tpu.util.testdata import make_traces

TENANT = "t"


# ----------------------------------------------------------------- parser


def test_parse_basic():
    q = parse('{ span.foo = "bar" }')
    c = q.expr
    assert isinstance(c, Comparison)
    assert c.field.scope == Scope.SPAN and c.field.name == "foo"
    assert c.op == "=" and c.value.value == "bar"


def test_parse_scopes_and_intrinsics():
    q = parse('{ resource.service.name = "x" && name = "y" && .cluster = "z" }')
    e = q.expr
    assert isinstance(e, LogicalExpr) and e.op == "&&"
    # left-assoc: ((a && b) && c)
    assert e.rhs.field.scope == Scope.EITHER and e.rhs.field.name == "cluster"
    assert e.lhs.lhs.field.scope == Scope.RESOURCE
    assert e.lhs.lhs.field.name == "service.name"
    assert e.lhs.rhs.field.scope == Scope.INTRINSIC


def test_parse_values():
    q = parse("{ duration > 1h30m && span.count >= 100 && span.ratio < 0.5 && span.ok = true }")
    comps = []

    def walk(e):
        if isinstance(e, LogicalExpr):
            walk(e.lhs)
            walk(e.rhs)
        else:
            comps.append(e)

    walk(q.expr)
    dur = comps[0]
    assert dur.value.kind == "duration" and dur.value.value == 5400 * 10**9
    assert comps[1].value.kind == "int" and comps[1].value.value == 100
    assert comps[2].value.kind == "float"
    assert comps[3].value.kind == "bool"


def test_parse_status_kind_regex():
    q = parse("{ status = error && kind = server }")
    assert q.expr.lhs.value.kind == "status" and q.expr.lhs.value.value == 2
    assert q.expr.rhs.value.kind == "kind" and q.expr.rhs.value.value == 2
    q2 = parse('{ span.http.url =~ "api/.*" }')
    assert q2.expr.op == "=~"


def test_parse_parens_and_or():
    q = parse('{ (span.a = "1" || span.b = "2") && name = "n" }')
    assert isinstance(q.expr, LogicalExpr) and q.expr.op == "&&"
    assert q.expr.lhs.op == "||"


def test_parse_reversed_operands():
    q = parse("{ 100 < span.count }")
    assert q.expr.field.name == "count" and q.expr.op == ">"


def test_parse_empty_and_exists():
    # `{}` is a parse error per the reference grammar (test_examples
    # parse_fails); a bare field is truthiness, not existence
    with pytest.raises(ParseError):
        parse("{}")
    q = parse("{ span.foo }")
    from tempo_tpu.traceql.ast import Field
    assert isinstance(q.expr, Field) and q.expr.name == "foo"
    q2 = parse("{ span.foo != nil }")
    assert q2.expr.op == "!=" and q2.expr.value.kind == "nil"


def test_parse_errors():
    for bad in ["span.x = 1", "{ span.x = }", "{", "{ true } | count()", '{ name = "x" } { }']:
        with pytest.raises(ParseError):
            parse(bad)


# ----------------------------------------------------------- execution


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    d = TempoDB(TempoDBConfig(wal_path=str(tmp_path_factory.mktemp("wal"))), backend=MemBackend())
    traces = make_traces(80, seed=21, n_spans=8)
    d.write_block(TENANT, traces)
    return d, traces


def _expect(traces, pred):
    return {tid.hex() for tid, t in traces if any(pred(res, sp) for res, _, sp in t.all_spans())}


def _run(db, q):
    return {r.trace_id for r in db.search(TENANT, SearchRequest(query=q, limit=1000)).traces}


def test_query_service_name(db):
    d, traces = db
    got = _run(d, '{ resource.service.name = "db" }')
    assert got == _expect(traces, lambda res, sp: res.service_name == "db")


def test_query_span_attr_and_duration(db):
    d, traces = db
    got = _run(d, '{ span.http.method = "GET" && duration > 500ms }')
    assert got == _expect(
        traces,
        lambda res, sp: sp.attrs.get("http.method") == "GET" and sp.duration_nanos > 500_000_000,
    )
    assert got  # non-trivial


def test_query_duration_exact_boundary(db):
    d, traces = db
    # pick an actual span duration and query strictly-greater: that span
    # must NOT match on its own duration
    tid0, t0 = traces[0]
    sp0 = next(t0.all_spans())[2]
    ns = sp0.duration_nanos
    got_gt = _run(d, f"{{ duration > {ns}ns }}")
    expect_gt = _expect(traces, lambda res, sp: sp.duration_nanos > ns)
    assert got_gt == expect_gt
    got_ge = _run(d, f"{{ duration >= {ns}ns }}")
    expect_ge = _expect(traces, lambda res, sp: sp.duration_nanos >= ns)
    assert got_ge == expect_ge
    assert tid0.hex() in got_ge


def test_query_int_attr(db):
    d, traces = db
    got = _run(d, "{ span.http.status_code >= 500 }")
    assert got == _expect(
        traces,
        lambda res, sp: isinstance(sp.attrs.get("http.status_code"), int)
        and sp.attrs["http.status_code"] >= 500,
    )


def test_query_status_error(db):
    d, traces = db
    got = _run(d, "{ status = error }")
    assert got == _expect(traces, lambda res, sp: sp.status_code == 2)


def test_query_or_and_parens(db):
    d, traces = db
    got = _run(d, '{ (resource.service.name = "db" || resource.service.name = "auth") && kind = client }')
    assert got == _expect(
        traces, lambda res, sp: res.service_name in ("db", "auth") and sp.kind == 3
    )


def test_query_regex(db):
    d, traces = db
    got = _run(d, '{ name =~ "GET.*" }')
    assert got == _expect(traces, lambda res, sp: sp.name.startswith("GET"))
    got2 = _run(d, '{ name !~ "GET.*" }')
    assert got2 == _expect(traces, lambda res, sp: not sp.name.startswith("GET"))


def test_query_neq_semantics(db):
    d, traces = db
    # != requires the attribute to EXIST and differ (TraceQL nil-compare is false)
    got = _run(d, '{ span.http.method != "GET" }')
    assert got == _expect(
        traces,
        lambda res, sp: "http.method" in sp.attrs and sp.attrs["http.method"] != "GET",
    )


def test_query_bool_attr(db):
    d, traces = db
    got = _run(d, "{ span.cache.hit = true }")
    assert got == _expect(traces, lambda res, sp: sp.attrs.get("cache.hit") is True)


def test_query_either_scope(db):
    d, traces = db
    got = _run(d, '{ .k8s.namespace.name = "apps" }')
    assert got == _expect(traces, lambda res, sp: res.attrs.get("k8s.namespace.name") == "apps")


def test_query_same_span_semantics(db):
    d, traces = db
    # spanset AND: both conditions on the SAME span
    got = _run(d, '{ span.http.method = "GET" && span.http.status_code = 500 }')
    assert got == _expect(
        traces,
        lambda res, sp: sp.attrs.get("http.method") == "GET"
        and sp.attrs.get("http.status_code") == 500,
    )


def test_tags_trace_level_semantics(db):
    """Tag search (unlike TraceQL) matches tags anywhere in the trace."""
    d, traces = db
    resp = d.search(TENANT, SearchRequest(tags={"service.name": "db", "http.method": "GET"}, limit=1000))

    def trace_pred(t):
        has_db = any(res.service_name == "db" for res, _, _ in t.all_spans())
        has_get = any(sp.attrs.get("http.method") == "GET" for _, _, sp in t.all_spans())
        return has_db and has_get

    assert {r.trace_id for r in resp.traces} == {tid.hex() for tid, t in traces if trace_pred(t)}


def test_query_nonexistent_prunes(db):
    d, traces = db
    assert _run(d, '{ span.nope = "nothing" }') == set()
    assert _run(d, '{ resource.service.name = "zzz-absent" }') == set()


# --------------------------------------------- regression: review findings


@pytest.fixture(scope="module")
def db2(tmp_path_factory):
    """Handcrafted traces for same-span / clamped-duration / escape cases."""
    from tempo_tpu.wire.model import Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace

    base = 1_700_000_000_000_000_000

    def mk(tid_byte, spans):
        tid = bytes([tid_byte]) * 16
        sps = []
        for i, (name, attrs, dur_ns) in enumerate(spans):
            sps.append(
                Span(
                    trace_id=tid,
                    span_id=bytes([i + 1]) * 8,
                    parent_span_id=b"" if i == 0 else bytes([1]) * 8,
                    name=name,
                    start_unix_nano=base,
                    end_unix_nano=base + dur_ns,
                    attrs=attrs,
                )
            )
        rs = ResourceSpans(
            resource=Resource(attrs={"service.name": "svc"}),
            scope_spans=[ScopeSpans(scope=Scope(), spans=sps)],
        )
        return tid, Trace(resource_spans=[rs])

    traces = [
        # t1: a and b on DIFFERENT spans, root name "root-a"
        mk(1, [("root-a", {"a": "v"}, 10_000), ("child", {"b": "v"}, 10_000)]),
        # t2: a and b on the SAME span
        mk(2, [("root-b", {"a": "v", "b": "v"}, 10_000)]),
        # t3: 50-minute span (dur_us clamps at ~35.8 min) + a short one
        mk(3, [("long-op", {}, 3000 * 10**9), ("short-op", {}, 5_000_000)]),
        # t4: newline in an attr value
        mk(4, [("esc", {"msg": "a\nb"}, 10_000)]),
    ]
    d = TempoDB(TempoDBConfig(wal_path=str(tmp_path_factory.mktemp("wal2"))), backend=MemBackend())
    d.write_block(TENANT, traces)
    return d, traces


def test_mixed_and_keeps_same_span_semantics(db2):
    """{spanA && spanB && traceC}: span conds must hold on ONE span even
    when a trace-level cond is ANDed in (normalize_tree grouping)."""
    d, _ = db2
    got = _run(d, '{ span.a = "v" && span.b = "v" }')
    assert got == {("\x02" * 16).encode("latin1").hex() if False else (bytes([2]) * 16).hex()}
    got = _run(d, '{ span.a = "v" && span.b = "v" && rootName = "root-b" }')
    assert got == {(bytes([2]) * 16).hex()}
    got = _run(d, '{ span.a = "v" && span.b = "v" && rootName = "root-a" }')
    assert got == set()


def test_clamped_duration_query(db2):
    """Durations past the int32-us clamp (~35.8 min) verify exactly."""
    d, _ = db2
    t3 = (bytes([3]) * 16).hex()
    assert _run(d, "{ duration > 40m }") == {t3}
    assert _run(d, "{ duration > 60m }") == set()
    assert _run(d, "{ duration >= 50m }") == {t3}
    # < past the clamp still finds the short spans (conservative + verify)
    assert t3 in _run(d, "{ duration < 45m }")


def test_string_escape_newline(db2):
    d, _ = db2
    assert _run(d, '{ span.msg = "a\\nb" }') == {(bytes([4]) * 16).hex()}
    assert _run(d, '{ span.msg = "a\\tb" }') == set()


def test_wellknown_resource_exists(db2):
    d, _ = db2
    # existence is `!= nil` (reference semantics: a BARE field is
    # boolean truthiness, so `{ resource.service.name }` matches nothing)
    assert len(_run(d, "{ resource.service.name != nil }")) == 4
    assert _run(d, "{ resource.k8s.pod.name != nil }") == set()
    assert _run(d, "{ resource.service.name }") == set()


def test_pipeline_aggregates_parse_and_eval():
    """`{...} | count()/avg()/... op N` scalar filters (expr.y pipeline
    stages), evaluated exactly on the wire model."""
    from tempo_tpu.traceql.ast import Pipeline
    from tempo_tpu.traceql.hosteval import trace_matches
    from tempo_tpu.traceql.parser import parse
    from tempo_tpu.wire.model import Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace

    def mk_trace(durs_ms, svc="api"):
        spans = [
            Span(trace_id=b"\x01" * 16, span_id=bytes([i] * 8), name=f"op{i}",
                 start_unix_nano=10**18, end_unix_nano=10**18 + d * 10**6,
                 attrs={"n": i})
            for i, d in enumerate(durs_ms)
        ]
        return Trace(resource_spans=[ResourceSpans(
            resource=Resource(attrs={"service.name": svc}),
            scope_spans=[ScopeSpans(scope=Scope(), spans=spans)])])

    q = parse("{ true } | count() > 2")
    assert isinstance(q, Pipeline)
    assert trace_matches(q, mk_trace([1, 2, 3]))
    assert not trace_matches(q, mk_trace([1, 2]))

    # aggregate over the filtered spanset, not all spans
    q = parse('{ duration > 5ms } | count() = 2')
    assert trace_matches(q, mk_trace([1, 10, 20]))
    assert not trace_matches(q, mk_trace([10, 20, 30]))

    q = parse("{ true } | avg(duration) >= 10ms")
    assert trace_matches(q, mk_trace([5, 15]))
    assert not trace_matches(q, mk_trace([5, 5]))

    q = parse("{ true } | max(duration) < 10ms | min(duration) > 1ms")
    assert trace_matches(q, mk_trace([2, 9]))
    assert not trace_matches(q, mk_trace([2, 19]))

    q = parse("{ true } | sum(span.n) = 3")
    assert trace_matches(q, mk_trace([1, 1, 1]))  # n = 0+1+2

    # empty spansets never reach the pipeline (reference semantics):
    # the live and block paths must agree
    q = parse("{ duration > 1s } | count() < 1")
    assert not trace_matches(q, mk_trace([1, 2]))

    import pytest as _pytest
    from tempo_tpu.traceql.ast import ParseError
    for bad in ("{ true } | count(duration) > 1", "{ true } | avg() > 1",
                "{ true } | p99() > 1", '{ true } | count() > "x"',
                "{ true } | avg(name) > 0",
                "{ true } | max(status) = 2"):
        with _pytest.raises(ParseError):
            parse(bad)


def test_pipeline_aggregates_e2e_search(tmp_path):
    """Pipelines run through the full search path: device spanset
    prefilter + exact host aggregate verification."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "wal")), backend=MemBackend())
    traces = make_traces(30, seed=17, n_spans=5)  # 5 spans each
    few = make_traces(6, seed=18, n_spans=2)  # 2 spans each
    db.write_block("t", sorted(traces + few, key=lambda t: t[0]))

    resp = db.search("t", SearchRequest(query="{ true } | count() > 3", limit=100))
    assert {t.trace_id for t in resp.traces} == {tid.hex() for tid, _ in traces}
    resp = db.search("t", SearchRequest(query="{ true } | count() <= 2", limit=100))
    assert {t.trace_id for t in resp.traces} == {tid.hex() for tid, _ in few}
    db.close()


def test_structural_operators():
    """`{a} > {b}`, `>>`, `~`, `&&`, `||` between spansets."""
    from tempo_tpu.traceql.hosteval import trace_matches
    from tempo_tpu.traceql.parser import parse
    from tempo_tpu.wire.model import Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace

    def sp(name, sid, parent=b""):
        return Span(trace_id=b"\x01" * 16, span_id=sid, parent_span_id=parent,
                    name=name, start_unix_nano=10**18, end_unix_nano=10**18 + 10**6)

    # a -> b -> c, plus sibling d of b
    a, b, c, d = (bytes([i] * 8) for i in (1, 2, 3, 4))
    spans = [sp("a", a), sp("b", b, a), sp("c", c, b), sp("d", d, a)]
    tr = Trace(resource_spans=[ResourceSpans(
        resource=Resource(attrs={"service.name": "s"}),
        scope_spans=[ScopeSpans(scope=Scope(), spans=spans)])])

    assert trace_matches(parse('{ name = "a" } > { name = "b" }'), tr)
    assert not trace_matches(parse('{ name = "a" } > { name = "c" }'), tr)  # not direct
    assert trace_matches(parse('{ name = "a" } >> { name = "c" }'), tr)  # descendant
    assert not trace_matches(parse('{ name = "c" } >> { name = "a" }'), tr)
    assert trace_matches(parse('{ name = "b" } ~ { name = "d" }'), tr)  # siblings
    assert not trace_matches(parse('{ name = "b" } ~ { name = "c" }'), tr)
    assert trace_matches(parse('{ name = "a" } && { name = "d" }'), tr)
    assert not trace_matches(parse('{ name = "a" } && { name = "zzz" }'), tr)
    assert trace_matches(parse('{ name = "zzz" } || { name = "d" }'), tr)
    # structural + pipeline: children of a == {b, d}
    assert trace_matches(parse('{ name = "a" } > { true } | count() = 2'), tr)
    assert not trace_matches(parse('{ name = "a" } > { true } | count() > 2'), tr)


def test_structural_e2e_search(tmp_path):
    """Structural queries through the full block search path: device
    leaf prefilter + exact host relation verification."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest
    from tempo_tpu.wire.model import Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace

    def mk(tid_byte, parent_child):
        tid = bytes([tid_byte]) * 16
        spans = []
        for i, (name, sid_b, parent_b) in enumerate(parent_child):
            spans.append(Span(
                trace_id=tid, span_id=bytes([sid_b] * 8) if isinstance(sid_b, int) else sid_b,
                parent_span_id=bytes([parent_b] * 8) if parent_b else b"",
                name=name, start_unix_nano=10**18 + i, end_unix_nano=10**18 + 10**6))
        return tid, Trace(resource_spans=[ResourceSpans(
            resource=Resource(attrs={"service.name": "s"}),
            scope_spans=[ScopeSpans(scope=Scope(), spans=spans)])])

    # t1: gateway -> db (direct); t2: gateway -> mid -> db; t3: db alone
    t1 = mk(1, [("gateway", 1, 0), ("db", 2, 1)])
    t2 = mk(2, [("gateway", 1, 0), ("mid", 2, 1), ("db", 3, 2)])
    t3 = mk(3, [("db", 1, 0)])
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "wal")), backend=MemBackend())
    db.write_block("t", sorted([t1, t2, t3], key=lambda t: t[0]))

    def search(q):
        return {t.trace_id for t in db.search("t", SearchRequest(query=q, limit=10)).traces}

    assert search('{ name = "gateway" } > { name = "db" }') == {t1[0].hex()}
    assert search('{ name = "gateway" } >> { name = "db" }') == {t1[0].hex(), t2[0].hex()}
    assert search('{ name = "gateway" } && { name = "mid" }') == {t2[0].hex()}
    assert search('{ name = "mid" } || { name = "db" }') == {t1[0].hex(), t2[0].hex(), t3[0].hex()}
    db.close()


def test_structural_precedence_and_twins():
    """expr.y precedence: > binds tighter than && ; ~ matches twin
    same-name siblings; zero-filled parents are not siblings."""
    from tempo_tpu.traceql.ast import SpansetOp
    from tempo_tpu.traceql.hosteval import trace_matches
    from tempo_tpu.traceql.parser import parse
    from tempo_tpu.wire.model import Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace

    q = parse('{ name = "a" } && { name = "b" } > { name = "c" }')
    assert isinstance(q, SpansetOp) and q.op == "&&"
    assert isinstance(q.rhs, SpansetOp) and q.rhs.op == ">"  # b > c under &&

    def sp(name, sid, parent=b""):
        return Span(trace_id=b"\x01" * 16, span_id=sid, parent_span_id=parent,
                    name=name, start_unix_nano=10**18, end_unix_nano=10**18 + 10**6)

    p, x1, x2 = bytes([9] * 8), bytes([1] * 8), bytes([2] * 8)
    twins = Trace(resource_spans=[ResourceSpans(
        resource=Resource(attrs={"service.name": "s"}),
        scope_spans=[ScopeSpans(scope=Scope(), spans=[
            sp("par", p), sp("x", x1, p), sp("x", x2, p)])])])
    assert trace_matches(parse('{ name = "x" } ~ { name = "x" }'), twins)

    roots = Trace(resource_spans=[ResourceSpans(
        resource=Resource(attrs={"service.name": "s"}),
        scope_spans=[ScopeSpans(scope=Scope(), spans=[
            sp("a", x1, b"\x00" * 8), sp("b", x2, b"\x00" * 8)])])])
    assert not trace_matches(parse('{ name = "a" } ~ { name = "b" }'), roots)


def test_parenthesized_spanset_expressions():
    from tempo_tpu.traceql.ast import SpansetOp
    from tempo_tpu.traceql.parser import parse

    q = parse('({ name = "a" } || { name = "b" }) > { name = "c" }')
    assert isinstance(q, SpansetOp) and q.op == ">"
    assert isinstance(q.lhs, SpansetOp) and q.lhs.op == "||"
    # without parens, || binds looser: a || (b > c)
    q2 = parse('{ name = "a" } || { name = "b" } > { name = "c" }')
    assert q2.op == "||" and q2.rhs.op == ">"


def test_grammar_tail_execution():
    """Execution semantics of the expr.y grammar tail: parent scope,
    childCount, field arithmetic, field-to-field compares, nil, bare
    fields, by()/coalesce(), scalar-pipeline expressions."""
    from tempo_tpu.traceql.hosteval import trace_matches
    from tempo_tpu.traceql.parser import parse
    from tempo_tpu.wire.model import Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace

    def sp(name, sid, parent=b"", dur_ms=10, attrs=None):
        return Span(trace_id=b"\x01" * 16, span_id=sid, parent_span_id=parent,
                    name=name, start_unix_nano=10**18,
                    end_unix_nano=10**18 + dur_ms * 10**6, attrs=attrs or {})

    a, b, c, d = (bytes([i] * 8) for i in (1, 2, 3, 4))
    spans = [
        sp("root", a, dur_ms=100, attrs={"x": 10, "flag": True, "svc": "api"}),
        sp("mid", b, a, dur_ms=50, attrs={"x": 4, "y": 4}),
        sp("leaf", c, b, dur_ms=5, attrs={"x": 7}),
        sp("leaf", d, b, dur_ms=5, attrs={"x": 3, "flag": False}),
    ]
    tr = Trace(resource_spans=[ResourceSpans(
        resource=Resource(attrs={"service.name": "s", "env": "prod"}),
        scope_spans=[ScopeSpans(scope=Scope(), spans=spans)])])

    m = lambda q: trace_matches(parse(q), tr)  # noqa: E731

    # childCount: root has 1 child (mid), mid has 2
    assert m("{ childCount = 2 }")
    assert m("{ 1 = childCount }")
    assert not m("{ childCount > 2 }")
    # parent intrinsic and parent-scoped attrs
    assert m("{ parent = nil }")  # the root
    assert m('{ parent.name = "mid" }')  # parent's intrinsic name
    assert m("{ parent.x = 4 }")  # leaf's parent is mid (x=4)
    assert m("{ parent.span.x = 10 }")  # mid's parent is root
    assert m('{ parent.resource.env = "prod" }')
    assert not m("{ parent.x = 99 }")
    # field arithmetic + field-to-field
    assert m("{ .x + 1 = 5 }")  # mid: 4+1
    assert m("{ .x * 2 = 20 }")  # root
    assert m("{ .x ^ 2 = 49 }")  # leaf: 7^2
    assert m("{ .x = .y }")  # mid: x=4, y=4
    assert not m("{ .x + .y = 999 }")
    assert m("{ -.x = -10 }")
    assert m("{ duration > 40ms && .x = 4 }")
    # nil and bare fields
    assert m("{ .flag }")  # root's flag is true
    assert not m("{ .y && .x = 10 }")  # y absent on root
    assert m("{ .y != nil }")  # mid has y
    assert m("{ .missing = nil }")
    assert not m("{ .x = nil }")
    # by()/coalesce(): group by name -> 2 leaf spans in one group
    assert m('{ true } | by(name) | count() = 2')
    assert m('{ true } | by(.x) | count() = 1 | coalesce() | count() = 4')
    assert not m('{ true } | by(name) | count() = 3')
    # scalar-pipeline expressions
    assert m('({ name =~ "leaf.*" } | count()) + ({ name = "mid" } | count()) = 3')
    assert m('({ true } | count()) > ({ name = "mid" } | count())')
    assert m('{ true } | count() + count() = 8')
    assert m('max(duration) - min(duration) > 90ms')
    assert m('avg(.x) = 6')  # (10+4+7+3)/4


def test_structural_device_pruning(tmp_path):
    """Pure structural queries compile to exact ('struct', ...) span
    trees over span.parent_idx: needs_verify is OFF, and the host and
    device engines agree with the wire-model evaluator on every block
    trace (VERDICT r3 item 3; reference ops:
    pkg/traceql/enum_operators.go OpSpansetChild/Descendant/Sibling)."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest, _plan_for_block, search_block
    from tempo_tpu.traceql.hosteval import trace_matches
    from tempo_tpu.traceql.parser import parse
    from tempo_tpu.util.testdata import make_traces

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")), backend=MemBackend())
    traces = make_traces(60, seed=33, n_spans=10)
    db.write_block(TENANT, traces)
    blk = db.open_block(db.blocklist.metas(TENANT)[0])

    queries = [
        '{ name = "GET /api" } > { true }',
        '{ true } > { name = "db.query" }',
        '{ name = "GET /api" } >> { name = "db.query" }',
        '{ name = "GET /api" } ~ { true }',
        '{ name = "GET /api" } > { true } >> { name = "db.query" }',
    ]
    for q in queries:
        p = _plan_for_block(blk, SearchRequest(query=q))
        # '~' trees keep verification (orphan-sibling over-match); the
        # parent/descendant relations are exact with no verify
        want_verify = "~" in q
        assert p.prune or (p.has_struct and p.needs_verify == want_verify), (q, p)
        want = {tid.hex() for tid, t in traces if trace_matches(parse(q), t)}
        got_h = {t.trace_id for t in
                 search_block(blk, SearchRequest(query=q, limit=1000), mode="host").traces}
        got_d = {t.trace_id for t in
                 search_block(blk, SearchRequest(query=q, limit=1000), mode="device").traces}
        assert got_h == want, (q, len(got_h), len(want))
        assert got_d == want, (q, len(got_d), len(want))

    # mixed structural (trace-level cond inside) still verifies
    p = _plan_for_block(blk, SearchRequest(query='{ traceDuration > 1ms } > { true }'))
    assert p.needs_verify and not p.has_struct


def test_structural_orphan_siblings(tmp_path):
    """Spans sharing a parent ID whose span was never ingested (orphans)
    are still siblings; the struct kernel over-matches them and host
    verification keeps the result exact."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest, search_block
    from tempo_tpu.traceql.hosteval import trace_matches
    from tempo_tpu.traceql.parser import parse
    from tempo_tpu.wire.model import Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace

    missing = b"\xaa" * 8
    spans = [
        Span(trace_id=b"\x07" * 16, span_id=bytes([i] * 8), parent_span_id=missing,
             name=n, start_unix_nano=10**18, end_unix_nano=10**18 + 10**6)
        for i, n in ((1, "a"), (2, "b"))
    ]
    tr = Trace(resource_spans=[ResourceSpans(
        resource=Resource(attrs={"service.name": "s"}),
        scope_spans=[ScopeSpans(scope=Scope(), spans=spans)])])
    q = '{ name = "a" } ~ { name = "b" }'
    assert trace_matches(parse(q), tr)

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")), backend=MemBackend())
    db.write_block(TENANT, [(b"\x07" * 16, tr)])
    blk = db.open_block(db.blocklist.metas(TENANT)[0])
    for mode in ("host", "device"):
        got = search_block(blk, SearchRequest(query=q, limit=10), mode=mode)
        assert len(got.traces) == 1, mode


@pytest.mark.parametrize("query", [
    "{ span.bytes.sent > 4000000000 }",   # operand past the int32 clamp
    "{ span.bytes.sent < 4000000000 }",
    "{ span.bytes.sent != 5000000000 }",
    "{ span.bytes.sent = 5000000000 }",
    "{ span.bytes.sent != 2147483647 }",  # operand AT the clamp
    "{ span.bytes.sent < -3000000000 }",
    "{ span.bytes.sent < 7.5 }",          # float operand, int attribute
    "{ span.bytes.sent > 6.5 }",
    "{ span.bytes.sent != 7.5 }",
    "{ span.bytes.sent = 7.5 }",
    "{ span.bytes.sent >= 7.0 }",
    "{ span.bytes.sent > 5 }",            # the exact case stays exact
])
def test_int_attribute_compare_never_under_matches(tmp_path, query):
    """The int column clamps to int32 and holds whole numbers: an operand
    at or past the clamp, or with a fraction, is compared conservatively
    on the device (never losing a row) and settled by hosteval. Both
    engines return the wire oracle's set."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest, _plan_for_block, search_block
    from tempo_tpu.traceql.hosteval import trace_matches
    from tempo_tpu.traceql.parser import parse
    from tempo_tpu.util.testdata import make_traces

    traces = make_traces(30, seed=3, n_spans=4)
    for i, (_, t) in enumerate(traces):
        next(t.all_spans())[2].attrs["bytes.sent"] = (5_000_000_000, 3_000_000_000, 7)[i % 3]
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")), backend=MemBackend())
    blk = db.open_block(db.write_block(TENANT, traces))
    want = {tid.hex() for tid, t in traces if trace_matches(parse(query), t)}
    int_cond = _plan_for_block(blk, SearchRequest(query=query)).conds[0]
    assert int_cond.col == "int" and int_cond.needs_verify == (query != "{ span.bytes.sent > 5 }")
    for mode in ("host", "device"):
        got = {t.trace_id for t in
               search_block(blk, SearchRequest(query=query, limit=1000), mode=mode).traces}
        assert got == want, (query, mode, len(got), len(want))
