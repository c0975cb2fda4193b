"""The one span primitive (kerneltel TEL.stage) and what rides on it:
three sinks and nesting, the device-trace endpoint without jax's Python
tracer, and a jax.named_scope("tempo.<op>") in every kernel's HLO."""

from __future__ import annotations

import io
import json
import re
import socket
import tempfile
import threading
import time
import types
import urllib.error
import urllib.parse
import urllib.request
import zipfile

import numpy as np
import pytest

from tempo_tpu.util.kerneltel import TEL


# ----------------------------------------------------------- TEL.stage


def _traced():
    """A live self-trace parked as the ambient one, and where its spans go."""
    from tempo_tpu.services.selftrace import SelfTracer

    shipped: list = []
    tracer = SelfTracer(push=lambda tenant, rs: shipped.append(rs))
    return tracer, shipped


def _spans_of(shipped) -> dict:
    out = {}
    for rs_list in shipped:
        for rs in rs_list:
            for ss in rs.scope_spans:
                for sp in ss.spans:
                    out[sp.name] = sp
    return out


def _run_in_trace(body) -> dict:
    tracer, shipped = _traced()
    with tracer.trace("frontend.search", {"tenant": "t"}) as t:
        tok = TEL.set_active_trace(t)
        try:
            body()
        finally:
            TEL.reset_active_trace(tok)
    tracer.flush()
    return _spans_of(shipped)


def _case_counter_only():
    """No trace, no session: the stage adds to the table and nothing else."""
    before = TEL.stage_stats().get("rows:materialize", {"count": 0, "seconds": 0.0})
    with TEL.stage("rows:materialize", rows=3) as st:
        time.sleep(0.002)
    assert st._span is None
    after = TEL.stage_stats()["rows:materialize"]
    assert after["count"] == before["count"] + 1
    assert after["seconds"] >= before["seconds"] + 0.002
    assert st.seconds >= 0.002
    assert TEL.snapshot()["stages"]["rows:materialize"] == after


def _case_nesting():
    """A child's parent is the enclosing stage; a top-level stage hangs
    off the ambient parent (here the root)."""
    def body():
        with TEL.stage("stage:assemble", block="abcd"):
            with TEL.stage("stream:upload", bytes=10):
                pass
    spans = _run_in_trace(body)
    outer, inner, root = spans["stage:assemble"], spans["stream:upload"], spans["frontend.search"]
    assert inner.parent_span_id == outer.span_id
    assert outer.parent_span_id == root.span_id
    assert outer.attrs["block"] == "abcd" and inner.attrs["bytes"] == 10


def _case_verify_leaf():
    """Stages recorded inside the verify leg are verify's SIBLINGS: the
    retroactive leaf keeps its whole self time (verify_ms_per_search)."""
    def body():
        t0 = time.time()
        with TEL.stage("rows:materialize", rows=2):
            time.sleep(0.01)
        with TEL.stage("verify:eval", rows=2):
            time.sleep(0.002)
        TEL.child_span("verify", t0, time.time(), {"rows": 2})
    spans = _run_in_trace(body)
    verify = spans["verify"]
    kids = [s for s in spans.values() if s.parent_span_id == verify.span_id]
    assert kids == []  # nothing to subtract from its self time
    for name in ("rows:materialize", "verify:eval"):
        assert spans[name].parent_span_id == verify.parent_span_id
        assert spans[name].start_unix_nano >= verify.start_unix_nano
        assert spans[name].end_unix_nano <= verify.end_unix_nano


def _case_late_attrs():
    """Attrs set in the body reach the span (known only at the end)."""
    def body():
        with TEL.stage("block:search", engine="device") as st:
            st.attrs["compile"] = True
    sp = _run_in_trace(body)["block:search"]
    assert sp.attrs["engine"] == "device" and sp.attrs["compile"] is True


def _case_families():
    """ingest:/stream:/generator: stages keep their /status/kernels
    sections and their histogram families."""
    with TEL.stage("ingest:decode"):
        pass
    with TEL.stage("stream:fetch"):
        pass
    with TEL.stage("generator:span-metrics"):
        pass
    assert TEL.ingest_stats()["stages"]["decode"]["count"] >= 1
    assert "fetch" in TEL.stream_stats()["stage_seconds"]
    assert TEL.generator_stats()["stages"]["span-metrics"]["count"] >= 1
    text = "\n".join(TEL.metrics_lines())
    for fam, stage in (("tempo_ingest_stage_seconds", "decode"),
                       ("tempo_stream_stage_seconds", "fetch"),
                       ("tempo_generator_stage_seconds", "span-metrics")):
        assert re.search(fam + r'_count\{stage="' + stage + r'"\} [1-9]', text), fam
    assert "decode" not in TEL.stage_stats()  # the table keeps full names
    assert "ingest:decode" in TEL.stage_stats()


def _case_launch():
    """TEL.launch = record_launch + a kernel:launch stage + the op's
    device-time window."""
    n0 = TEL.stage_stats().get("kernel:launch", {"count": 0})["count"]
    with TEL.launch("unit_op", ("unit_op", 7), 1024) as ln:
        assert ln.attrs["compile"] is True and ln.attrs["op"] == "unit_op"
        assert ln.sync(np.zeros(2)).shape == (2,)
    with TEL.launch("unit_op", ("unit_op", 7), 1024) as ln:
        assert ln.attrs["compile"] is False
    row = next(k for k in TEL.snapshot()["kernels"] if k["op"] == "unit_op")
    assert (row["compiles"], row["cache_hits"], row["calls"]) == (1, 1, 2)
    assert TEL.stage_stats()["kernel:launch"]["count"] == n0 + 2
    assert TEL.last_launch() == ("unit_op", "1024", False)


def _case_raises():
    """A body that raises is still timed, marks its span, and re-raises."""
    def body():
        with pytest.raises(ValueError):
            with TEL.stage("plan:compile"):
                raise ValueError("bad query")
    n0 = TEL.stage_stats().get("plan:compile", {"count": 0})["count"]
    sp = _run_in_trace(body)["plan:compile"]
    assert sp.attrs["error"] is True
    assert TEL.stage_stats()["plan:compile"]["count"] == n0 + 1


def _case_annotation():
    """Under a profiler session the stage lands on the host plane as
    tempo/<name> with its attrs."""
    import jax

    d = tempfile.mkdtemp(prefix="tempo-stage-ann-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with TEL.stage("rows:materialize", rows=5):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
    events = _host_events(ProfileData.from_file(path))
    assert events["tempo/rows:materialize"]["rows"] == 5


def _case_uncounted():
    """A body that did none of the stage's work (a live-head refresh that
    found nothing dirty) keeps its span and leaves the table alone."""
    n0 = TEL.ingest_stats()["stages"].get("stage_delta", {"count": 0})["count"]
    def body():
        for dirty in (False, True):
            with TEL.stage("ingest:stage_delta") as st:
                st.counted = dirty
    assert "ingest:stage_delta" in _run_in_trace(body)
    assert TEL.ingest_stats()["stages"]["stage_delta"]["count"] == n0 + 1


def _case_miss_reason():
    """stage:lookup's `hit` / `reason` / `missing` say what the lookup
    found resident, and a lookup reads a store other threads insert into
    and evict from without ever raising."""
    from tempo_tpu.backend import MemBackend
    from tempo_tpu.block import build_block_from_traces, open_block
    from tempo_tpu.ops import stage
    from tempo_tpu.util.testdata import make_traces

    backend = MemBackend()
    meta = build_block_from_traces(backend, "t", make_traces(30, seed=5, n_spans=4))
    blk = open_block(backend, "t", meta.block_id)

    def lookup(cols):
        a = _run_in_trace(lambda: stage.stage_block(blk, cols))["stage:lookup"].attrs
        return a["hit"], a["reason"], a["missing"]

    got = lookup(["span.dur_us", "trace.span_off"])
    assert got == (False, "not_staged", 2)
    got = lookup(["span.dur_us", "span.name_id", "trace.span_off"])
    assert got == (False, "partial_columns", 1)
    got = lookup(["trace.span_off", "span.name_id"])  # any spelling, any order
    assert got == (True, "", 0)
    stop = threading.Event()
    budget = stage.staged_cache_stats()["budget_bytes"]

    def churn():
        while not stop.is_set():
            stage.set_staged_cache_budget(1)
            stage.set_staged_cache_budget(budget)

    th = threading.Thread(target=churn, daemon=True)
    th.start()
    try:
        for _ in range(200):
            view = stage.stage_block(blk, ["span.dur_us", "trace.span_off"])
            assert set(view.cols) == {"span.dur_us", "trace.span_off"}
    finally:
        stop.set()
        th.join(10)
        stage.set_staged_cache_budget(budget)
    assert not th.is_alive()


def _spin(cpu_s: float) -> None:
    """Burn `cpu_s` seconds of THIS thread's CPU in Python."""
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        sum(range(200))


def _row(name: str) -> dict:
    return TEL.stage_stats().get(name, {"count": 0, "seconds": 0.0, "cpu_seconds": 0.0})


def _case_cpu_busy():
    """A body that computes was on a CPU for about as long as it took."""
    b = _row("verify:eval")
    with TEL.stage("verify:eval") as st:
        _spin(0.03)
    a = _row("verify:eval")
    assert 0.03 <= st.cpu_seconds <= st.seconds + 1e-3
    assert a["cpu_seconds"] - b["cpu_seconds"] == pytest.approx(st.cpu_seconds, abs=2e-6)
    assert a["seconds"] - b["seconds"] == pytest.approx(st.seconds, abs=2e-6)


def _case_cpu_sleeping():
    """A body that waits is off the CPU: seconds - cpu_seconds is the wait."""
    b = _row("stream:fetch")
    with TEL.stage("stream:fetch") as st:
        time.sleep(0.05)
    a = _row("stream:fetch")
    assert st.seconds >= 0.05 and st.cpu_seconds < 0.01
    assert a["cpu_seconds"] - b["cpu_seconds"] < 0.01


def _case_cpu_nested():
    """A nested stage lies inside its parent on both clocks, and both
    spans say `cpu_ms` (a stage without attrs of its own too)."""
    got = {}

    def body():
        with TEL.stage("topk:collect") as outer:
            _spin(0.01)
            with TEL.stage("rows:materialize", rows=1) as inner:
                _spin(0.01)
                time.sleep(0.01)
        got.update(outer=outer, inner=inner)
    spans = _run_in_trace(body)
    outer, inner = got["outer"], got["inner"]
    assert 0.01 <= inner.cpu_seconds <= outer.cpu_seconds - 0.01 + 1e-3
    assert inner.seconds <= outer.seconds
    assert inner.seconds - inner.cpu_seconds >= 0.009  # the sleep
    for name, st in (("topk:collect", outer), ("rows:materialize", inner)):
        assert spans[name].attrs["cpu_ms"] == pytest.approx(st.cpu_seconds * 1e3, abs=1e-3)
    assert "cpu_ms" not in outer.attrs  # the span's, not the annotation's


def _case_cpu_pool():
    """A stage's clock is its own thread's: work a body hands to a pool
    is in the pool thread's stage (its row, its span's `cpu_ms`) and the
    body reads as off the CPU while it waits for it."""
    from tempo_tpu.util.ctxpool import ContextThreadPool

    pool = ContextThreadPool(max_workers=2)
    got = {}

    def item(_):
        with TEL.stage("block:metrics") as st:
            _spin(0.01)
        return st

    def body():
        with TEL.stage("run:metrics_query_range") as outer:
            kids = list(pool.map(item, range(2)))
        got.update(outer=outer, kids=kids)
    b = _row("block:metrics")
    try:
        spans = _run_in_trace(body)
    finally:
        pool.shutdown()
    outer, kids = got["outer"], got["kids"]
    assert outer.cpu_seconds < 0.01 <= outer.seconds  # it only waited
    assert all(k.cpu_seconds >= 0.01 for k in kids)
    a = _row("block:metrics")
    assert a["cpu_seconds"] - b.get("cpu_seconds", 0.0) == pytest.approx(
        sum(k.cpu_seconds for k in kids), abs=2e-6)
    assert set(a) == {"count", "seconds", "cpu_seconds"}
    assert spans["block:metrics"].attrs["cpu_ms"] >= 10.0
    assert spans["run:metrics_query_range"].attrs["cpu_ms"] < 10.0
    # the pool's thread carried the context: its stage hangs under the body's
    assert spans["block:metrics"].parent_span_id == spans["run:metrics_query_range"].span_id


def _case_cpu_reuse():
    """The CPU clock is a system call (5.8 us on the chip's host): stage
    boundaries that follow each other within CPU_REUSE_S on one thread
    share a read; one that comes later takes its own, and so does
    another thread."""
    from tempo_tpu.util import kerneltel

    reads = []
    shim = types.SimpleNamespace(**{k: getattr(time, k) for k in dir(time)
                                    if not k.startswith("__")})
    shim.thread_time = lambda: (reads.append(threading.get_ident()), time.thread_time())[1]
    real, kerneltel.time = kerneltel.time, shim
    try:
        time.sleep(2 * kerneltel.CPU_REUSE_S)
        with TEL.stage("topk:collect"):
            with TEL.stage("rows:materialize"):
                pass
        assert len(reads) < 4, reads  # four boundaries, a cluster or two
        time.sleep(2 * kerneltel.CPU_REUSE_S)
        del reads[:]
        with TEL.stage("topk:collect") as st:
            _spin(0.003)
        assert len(reads) == 2 and st.cpu_seconds >= 0.003
        t = threading.Thread(target=lambda: TEL.stage("verify:eval").__enter__().__exit__(None, None, None))
        t.start()
        t.join()
        assert len(set(reads)) == 2  # the other thread read its own clock
    finally:
        kerneltel.time = real


def _case_cpu_raises():
    """A body that raises is counted on both clocks."""
    b = _row("plan:compile")
    with pytest.raises(ValueError):
        with TEL.stage("plan:compile"):
            _spin(0.01)
            raise ValueError("bad query")
    a = _row("plan:compile")
    assert a["count"] == b["count"] + 1
    assert a["cpu_seconds"] >= b["cpu_seconds"] + 0.01
    assert a["seconds"] >= b["seconds"] + 0.01


def _case_cpu_uncounted():
    """`counted = False` adds to neither clock."""
    with TEL.stage("ingest:stage_delta"):
        pass
    b = _row("ingest:stage_delta")
    with TEL.stage("ingest:stage_delta") as st:
        _spin(0.005)
        st.counted = False
    assert _row("ingest:stage_delta") == b and st.cpu_seconds >= 0.005


def _case_cpu_retroactive():
    """Rows and spans measured by their caller have no thread to ask:
    no `cpu_seconds` key, no `cpu_ms` attribute (absent, never 0)."""
    TEL.record_stage("job:dispatch", 0.25)
    TEL.record_stage("job:result", 0.5)
    for name in ("job:dispatch", "job:result"):
        row = TEL.stage_stats()[name]
        assert set(row) == {"count", "seconds"}, row

    def body():
        t0 = time.time()
        TEL.child_span("verify", t0, t0 + 0.1, {"rows": 2})
        TEL.child_span("queue-wait", t0, t0 + 0.1)
    spans = _run_in_trace(body)
    for name in ("verify", "queue-wait"):
        assert "cpu_ms" not in spans[name].attrs
    assert "verify" not in TEL.stage_stats()


def _case_cpu_at_session():
    """The table kept when a device-trace session begins carries the CPU
    clock, and so do the layer sections."""
    with TEL.stage("http:find"):
        _spin(0.002)
    with TEL.stage("ingest:decode"):
        pass
    with TEL.stage("generator:window"):
        pass
    TEL.mark_session()
    snap = TEL.snapshot()
    at = snap["stages_at_session"]["http:find"]
    assert at["cpu_seconds"] >= 0.002 and at == snap["stages"]["http:find"]
    assert "cpu_seconds" in snap["ingest"]["stages"]["decode"]
    assert "cpu_seconds" in snap["generator"]["stages"]["window"]
    with TEL.stage("http:find"):
        _spin(0.002)
    assert TEL.snapshot()["stages"]["http:find"]["cpu_seconds"] >= at["cpu_seconds"] + 0.002
    assert TEL.snapshot()["stages_at_session"]["http:find"] == at


_STAGE_CASES = {
    "counter_only": _case_counter_only, "nesting": _case_nesting,
    "verify_leaf": _case_verify_leaf, "late_attrs": _case_late_attrs,
    "families": _case_families, "launch": _case_launch,
    "raises": _case_raises, "annotation": _case_annotation,
    "uncounted": _case_uncounted, "miss_reason": _case_miss_reason,
    "cpu_busy": _case_cpu_busy, "cpu_sleeping": _case_cpu_sleeping,
    "cpu_nested": _case_cpu_nested, "cpu_raises": _case_cpu_raises,
    "cpu_pool": _case_cpu_pool, "cpu_reuse": _case_cpu_reuse,
    "cpu_uncounted": _case_cpu_uncounted, "cpu_retroactive": _case_cpu_retroactive,
    "cpu_at_session": _case_cpu_at_session,
}


@pytest.mark.parametrize("case", sorted(_STAGE_CASES))
def test_stage(case):
    _STAGE_CASES[case]()


# ------------------------------------------------------- run:<kind> stages


class _StageLog:
    """Every stage entered while installed, with the stages the same
    thread was already inside: (thread id, name, names around it)."""

    def __init__(self, monkeypatch):
        from tempo_tpu.util import kerneltel

        self.rows: list = []
        tls = threading.local()
        enter, leave = kerneltel._Stage.__enter__, kerneltel._Stage.__exit__

        def _enter(st):
            stack = tls.__dict__.setdefault("stack", [])
            self.rows.append((threading.get_ident(), st.name, tuple(stack)))
            stack.append(st.name)
            return enter(st)

        def _leave(st, *exc):
            tls.stack.pop()
            return leave(st, *exc)

        monkeypatch.setattr(kerneltel._Stage, "__enter__", _enter)
        monkeypatch.setattr(kerneltel._Stage, "__exit__", _leave)
        monkeypatch.setattr(kerneltel._Launch, "__exit__",
                            lambda st, *exc: (_leave(st, *exc), False)[1])

    def runs(self) -> list:
        return [r for r in self.rows if r[1].startswith("run:")]

    def assert_outermost(self):
        """No run:* inside another run:* or inside an http:* stage of
        its own thread: with the http:* roots they are the outermost
        stages of all request work."""
        for _, name, around in self.runs():
            assert not [a for a in around if a.startswith(("run:", "http:"))], (name, around)
        for _, name, around in self.rows:
            if name.startswith("http:") and name.split(":")[1] in (
                    "find", "search", "metrics", "push", "flush"):
                assert not [a for a in around if a.startswith("run:")], (name, around)


def _run_rows() -> dict:
    return {n: r for n, r in TEL.stage_stats().items() if n.startswith("run:")}


def _local_busy() -> float:
    return TEL.dispatch_stats()["by_worker"].get("local", {}).get("busy_seconds", 0.0)


def _bare_frontend():
    from tempo_tpu.services.frontend import Frontend

    return Frontend(querier=types.SimpleNamespace(db=None), n_workers=0)


def _site_execute_one(log):
    """One `run:<kind>` row a job, on the thread that ran it, and the
    local worker's busy seconds are the stage's."""
    from tempo_tpu.services.frontend import _Job

    fe = _bare_frontend()
    try:
        def engine():
            with TEL.stage("block:search"):
                _spin(0.005)
            return "answer"

        jobs = [_Job(kind=k, payload={}, fn=engine, args=())
                for k in ("search_recent", "find_recent", "metrics_query_range")]
        failing = _Job(kind="find_recent", payload={}, fn=lambda: 1 / 0, args=())
        for j in jobs:
            fe._execute_one("t", j)
        assert [j.result for j in jobs] == ["answer"] * 3
        assert _local_busy() == pytest.approx(
            sum(r["seconds"] for r in _run_rows().values()), abs=1e-5)
        fe._execute_one("t", failing)
        assert isinstance(failing.error, ZeroDivisionError)
    finally:
        fe.stop()
    rows = _run_rows()
    assert {n: r["count"] for n, r in rows.items()} == {
        "run:search_recent": 1, "run:find_recent": 2, "run:metrics_query_range": 1}
    assert all(r["cpu_seconds"] >= 0.005 for n, r in rows.items() if n != "run:find_recent")
    # the job that raised is timed, and it is not a completed job
    assert TEL.dispatch_stats()["by_worker"]["local"]["jobs"] == 3
    assert [n for _, n, _ in log.runs()] == [
        "run:search_recent", "run:find_recent", "run:metrics_query_range", "run:find_recent"]
    assert ("run:search_recent",) in [a for _, n, a in log.rows if n == "block:search"]


def _site_execute_batch(log):
    """Same-key jobs run as one call are one stage with jobs=N; each job
    takes its share of it as busy seconds."""
    from tempo_tpu.services.frontend import _Job

    fe = _bare_frontend()
    seen = {}
    try:
        def fused(group):
            _spin(0.006)
            return [f"r{i}" for i in range(len(group))]

        jobs = [_Job(kind="search_blocks", payload={}, fn=None, args=(),
                     batch_key=("k",), batch_fn=fused) for _ in range(3)]
        tracer, shipped = _traced()
        with tracer.trace("frontend.search", {"tenant": "t"}) as t:
            jobs[0].trace = t
            fe._execute_batch([("t", j) for j in jobs])
        tracer.flush()
        seen = _spans_of(shipped)
        assert [j.result for j in jobs] == ["r0", "r1", "r2"]
    finally:
        fe.stop()
    assert _run_rows()["run:search_blocks"]["count"] == 1
    assert _local_busy() == pytest.approx(_run_rows()["run:search_blocks"]["seconds"], abs=1e-5)
    assert seen["run:search_blocks"].attrs["jobs"] == 3
    assert seen["run:search_blocks"].attrs["cpu_ms"] >= 6.0
    assert TEL.dispatch_stats()["by_worker"]["local"]["jobs"] == 3
    assert len(log.runs()) == 1


def _site_worker(log):
    """The querier's pull loop: the process that runs the job records
    the row (one a wire job, a `multi` one under its jobs' kind)."""
    from tempo_tpu.services import worker as W

    wire_jobs = [
        {"id": "a", "tenant": "t", "kind": "find_recent",
         "payload": {"trace_id": "00" * 16}},
        {"id": "b", "tenant": "t", "kind": "multi",
         "payload": {"kind": "find_blocks", "tenants": ["t", "t"],
                     "jobs": [{"trace_id": "00" * 16, "block_ids": []}] * 2}},
        {"id": "c", "tenant": "t", "kind": "no_such_kind", "payload": {}},
        # malformed wire jobs are reported as failed, like any job that
        # raises: they must not end the pull loop
        {"id": "d", "tenant": "t", "kind": "multi", "payload": "not a dict"},
        {"id": "e", "tenant": "t", "kind": ["unhashable"], "payload": {}},
    ]

    class Blocklist:
        def metas_by_id(self, tenant, ids):
            return []

    class Querier:
        db = type("Db", (), {"blocklist": Blocklist()})()

        def find_trace_by_id(self, *a, **kw):
            _spin(0.004)

        def find_in_blocks(self, *a, **kw):
            _spin(0.002)

        def find_in_blocks_multi(self, items):
            _spin(0.004)
            return [None] * len(items)

    w = W.QuerierWorker(Querier(), ["http://frontend.invalid"], concurrency=1,
                        worker_id="querier-1")
    posted = []

    def post(addr, path, payload, timeout):
        if path.endswith("/poll"):
            if wire_jobs:
                return wire_jobs.pop(0)
            w.stop()
            return None
        posted.append(payload)

    w._post = post
    w._loop("http://frontend.invalid")
    assert [(p["id"], p["ok"]) for p in posted] == [
        ("a", True), ("b", True), ("c", False), ("d", False), ("e", False)]
    assert posted[1]["result"] == {"results": [{"trace": None}] * 2}
    rows = _run_rows()
    assert {n: r["count"] for n, r in rows.items()} == {
        "run:find_recent": 1, "run:find_blocks": 1, "run:unknown": 1}
    assert rows["run:find_recent"]["cpu_seconds"] >= 0.004
    assert [a for _, n, a in log.runs()] == [(), (), ()]
    assert "local" not in TEL.dispatch_stats()["by_worker"]  # the frontend's to count


_RUN_SITES = {"execute_one": _site_execute_one, "execute_batch": _site_execute_batch,
              "worker": _site_worker}


@pytest.mark.parametrize("site", sorted(_RUN_SITES))
def test_run_stage(site, monkeypatch):
    TEL.reset()
    log = _StageLog(monkeypatch)
    try:
        _RUN_SITES[site](log)
        log.assert_outermost()
    finally:
        TEL.reset()


# ------------------------------------------------ /debug/profile/device


def _host_events(data) -> dict:
    """event name -> its stats (last one wins), over the host planes."""
    out = {}
    for p in data.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                out[e.name] = dict(e.stats)
    return out


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One app over HTTP with a flushed block to find and search."""
    from tempo_tpu.services.app import App, AppConfig
    from tempo_tpu.services.ingester import IngesterConfig
    from tempo_tpu.util.testdata import make_traces
    from tempo_tpu.wire import otlp_json

    tmp = tmp_path_factory.mktemp("tracing-app")
    import os

    old = os.environ.get("TEMPO_PROFILE_DIR")
    os.environ["TEMPO_PROFILE_DIR"] = str(tmp / "profiles")
    cfg = AppConfig(
        storage_path=str(tmp / "store"), http_port=_free_port(),
        compaction_cycle_s=9999,
        ingester=IngesterConfig(max_trace_idle_s=0.0, max_block_age_s=0.0,
                                flush_check_period_s=9999))
    app = App(cfg)
    app.start()
    app.serve_http(background=True)
    base = f"http://127.0.0.1:{cfg.http_port}"
    traces = make_traces(8, seed=21, n_spans=4)
    for _, tr in traces:
        urllib.request.urlopen(urllib.request.Request(
            base + "/v1/traces", data=otlp_json.dumps(tr).encode(),
            headers={"Content-Type": "application/json"}), timeout=30)
    app.ingester.flush_all()
    app.db.poll_now()
    ids = [tid.hex() for tid, _ in traces]
    # warm: a 0.3 s session must not be spent inside one first compile
    q = urllib.parse.quote("{ duration > 1ms }")
    for _ in range(2):
        urllib.request.urlopen(f"{base}/api/traces/{ids[0]}", timeout=60).read()
        urllib.request.urlopen(f"{base}/api/search?q={q}&limit=5", timeout=120).read()
    yield base, ids
    app.stop()
    if old is None:
        os.environ.pop("TEMPO_PROFILE_DIR", None)
    else:
        os.environ["TEMPO_PROFILE_DIR"] = old


@pytest.mark.parametrize("python", [0, 1])
def test_device_profile_artifact(served, python):
    """?seconds=0.3 on the CPU backend: the session annotation with its
    wall-clock anchor, the served path's tempo/ annotations, and Python
    frames only when asked for."""
    from jax.profiler import ProfileData

    base, ids = served
    stop = threading.Event()

    def traffic():
        q = urllib.parse.quote("{ duration > 1ms }")
        i = 0
        while not stop.is_set():
            urllib.request.urlopen(f"{base}/api/traces/{ids[i % len(ids)]}", timeout=30).read()
            urllib.request.urlopen(f"{base}/api/search?q={q}&limit=5", timeout=60).read()
            i += 1

    th = threading.Thread(target=traffic, daemon=True)
    th.start()
    try:
        url = f"{base}/debug/profile/device?seconds=0.3" + ("&python=1" if python else "")
        t_before = time.time_ns()
        with urllib.request.urlopen(url, timeout=120) as r:
            out = json.loads(r.read())
    finally:
        stop.set()
        th.join(30)
    assert out["python"] is bool(python) and out["stop_s"] >= 0
    with urllib.request.urlopen(
            f"{base}/debug/profile/artifact/{out['artifact_id']}", timeout=60) as r:
        blob = r.read()
    with zipfile.ZipFile(io.BytesIO(blob)) as z:
        name = next(n for n in z.namelist() if n.endswith(".xplane.pb"))
        events = _host_events(ProfileData.from_serialized_xspace(z.read(name)))
    session = events["tempo/profile:session"]
    assert t_before <= int(session["unix_ns"]) <= time.time_ns()
    assert float(session["seconds"]) == pytest.approx(0.3)
    ours = {n for n in events if n.startswith("tempo/") and n != "tempo/profile:session"}
    assert {"tempo/http:find", "tempo/http:search"} & ours, sorted(ours)
    frames = [n for n in events if n.startswith("$")]
    assert bool(frames) is bool(python), frames[:5]
    # the table as it stood when the session began, beside the live one
    with urllib.request.urlopen(base + "/status/kernels", timeout=30) as r:
        snap = json.loads(r.read())
    at, now = snap["stages_at_session"]["http:find"], snap["stages"]["http:find"]
    assert 0 < at["count"] < now["count"] and at["seconds"] < now["seconds"]


def test_stages_table_over_http(served):
    """/status/kernels publishes the table the benchmark's readers diff,
    with the served routes' roots and the find path's stages in it, and
    ingest.stages keeps its keys."""
    base, ids = served
    urllib.request.urlopen(f"{base}/api/traces/{ids[0]}", timeout=30).read()
    with urllib.request.urlopen(base + "/status/kernels", timeout=30) as r:
        snap = json.loads(r.read())
    stages = snap["stages"]
    for name in ("http:find", "http:push", "http:encode", "http:write",
                 "find:bloom", "find:lookup", "find:fetch", "rows:materialize",
                 "ingest:lock_wait", "ingest:swap", "ingest:cut", "ingest:flush",
                 "cut:write"):
        assert stages[name]["count"] >= 1, name
    assert stages["find:fetch"]["seconds"] <= stages["http:find"]["seconds"]
    assert {"decode", "wal_append", "swap", "cut", "flush"} <= set(snap["ingest"]["stages"])
    # stream.stage_seconds counts the cold-read pipeline's units alone: a
    # warm staging miss uploads under stage:upload
    assert stages.get("stage:upload", {"count": 0})["count"] >= snap["stream"]["units"]
    assert ("upload" in snap["stream"]["stage_seconds"]) is bool(
        stages.get("stream:upload"))


def test_request_roots_are_outermost_over_http(served, monkeypatch):
    """Served requests: every job of a find, a search and a rate() ran in
    one run:<kind> stage on a worker's thread, none inside another or
    under the handler's http:* stage; the roots' CPU fits in the
    process's (`interp`), which /status/kernels publishes."""
    base, ids = served

    def status():
        with urllib.request.urlopen(base + "/status/kernels", timeout=30) as r:
            return json.loads(r.read())

    before = status()
    log = _StageLog(monkeypatch)
    q = urllib.parse.quote("{ duration > 2ms }")  # not what the fixture cached
    m = urllib.parse.quote("{ true } | rate()")
    t_s = 1_700_000_000  # make_traces' date
    with pytest.raises(urllib.error.HTTPError):  # an id nobody cached: a 404
        urllib.request.urlopen(f"{base}/api/traces/{'5a' * 16}", timeout=60).read()
    urllib.request.urlopen(f"{base}/api/search?q={q}&limit=5", timeout=120).read()
    urllib.request.urlopen(f"{base}/api/metrics/query_range?q={m}&start={t_s - 600}"
                           f"&end={t_s + 3000}&step=60", timeout=120).read()
    monkeypatch.undo()
    after = status()
    log.assert_outermost()
    kinds = {n for _, n, _ in log.runs()}
    assert {"run:find_recent", "run:search_recent",
            "run:metrics_query_range"} <= kinds, kinds
    assert kinds & {"run:search_blocks", "run:search_block_shard"}, kinds
    handlers = {tid for tid, n, _ in log.rows if n.startswith("http:")}
    assert not handlers & {tid for tid, _, _ in log.runs()}

    def delta(name, key):
        return after["stages"][name][key] - before["stages"].get(name, {}).get(key, 0)

    roots = [n for n in after["stages"]
             if n.startswith("run:") or n in ("http:find", "http:search", "http:metrics")]
    n_runs = sum(delta(n, "count") for n in roots if n.startswith("run:"))
    jobs = after["dispatch"]["jobs"]["local"] - before["dispatch"]["jobs"]["local"]
    assert n_runs == len(log.runs()) == jobs
    busy = (after["dispatch"]["by_worker"]["local"]["busy_seconds"]
            - before["dispatch"]["by_worker"]["local"]["busy_seconds"])
    assert busy == pytest.approx(
        sum(delta(n, "seconds") for n in roots if n.startswith("run:")), abs=1e-4)
    ia, ib = after["interp"], before["interp"]
    assert set(ia) == {"cpu_seconds", "wall_seconds", "probe"}
    assert set(ia["probe"]) == {"ticks", "late_seconds", "late_over_5ms", "late_over_20ms"}
    cpu = ia["cpu_seconds"] - ib["cpu_seconds"]
    assert 0 < sum(delta(n, "cpu_seconds") for n in roots) <= cpu + 1e-3
    assert ia["wall_seconds"] > ib["wall_seconds"]


# ------------------------------------------------- named kernel scopes

# op -> what the scenario below drives to make jax lower that kernel
_SCOPED_OPS = [
    "filter", "select", "res_to_span", "timeseries", "multiquery", "mq_select",
    "find", "cut_remap", "cut_bloom", "cut_rowgroups", "reduce", "edge_reduce",
    "bloom_union", "bloom_test", "live_filter", "live_find", "live_append",
    "live_patch", "mesh_find", "mesh_bloom", "mesh_search", "mesh_multiquery",
]


def _drive_kernels():
    """Lower (and run, tiny) one program of every kernel family."""
    import jax.numpy as jnp

    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.block.bloom import ShardedBloom
    from tempo_tpu.db.metrics_exec import (
        MetricsResponse, align_params, metrics_block, parse_metrics_query)
    from tempo_tpu.db.search import SearchRequest, _plan_for_block, search_block
    from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
    from tempo_tpu.ops import blockcut, bloom_ops, livestage, reduce as reduce_ops
    from tempo_tpu.ops.filter import Cond, Operands, T_RES, T_SPAN, required_columns
    from tempo_tpu.ops.find import lookup_ids, lookup_ids_blocks
    from tempo_tpu.ops.multiquery import (
        _p2, eval_multiquery, lower_plan, pack_queries, select_multiquery)
    from tempo_tpu.ops.stage import stage_block
    from tempo_tpu.parallel import make_mesh
    from tempo_tpu.parallel.bloom import sharded_bloom_union
    from tempo_tpu.parallel.find import sharded_find_rows
    from tempo_tpu.parallel.multiquery import mesh_eval_multiquery
    from tempo_tpu.parallel.search import sharded_search
    from tempo_tpu.util.testdata import make_traces

    mesh = make_mesh(8)
    db = TempoDB(TempoDBConfig(wal_path=tempfile.mkdtemp(prefix="tempo-scope-wal")),
                 backend=MemBackend())
    meta = db.write_block("t", make_traces(130, seed=17, n_spans=7))
    blk = db.open_block(meta)
    # filter + select + res_to_span; timeseries
    search_block(blk, SearchRequest(tags={"service.name": "db"}, limit=10), mode="device")
    base_s = meta.start_time_unix_nano // 1_000_000_000
    req = align_params("{ true } | rate()", base_s, base_s + 60, 10)
    resp = MetricsResponse(fn="rate", start_ms=req.start_ms, step_ms=req.step_ms,
                           n_buckets=req.n_buckets)
    metrics_block(blk, parse_metrics_query(req.query), req, resp, mode="device")
    # multiquery + mq_select, on one chip and on the mesh
    p = _plan_for_block(blk, SearchRequest(query='{ name = "db.query" }'))
    lq = lower_plan(p)
    staged = stage_block(blk, required_columns(p.conds) + list(p.extra_cols)
                         + ["trace.start_ms"])
    progs = pack_queries([lq], _p2(1, lo=1))
    tm, counts = eval_multiquery([lq], staged, progs)
    select_multiquery(tm, staged.cols["trace.start_ms"], counts, 16)
    mesh_eval_multiquery(mesh, [lq], staged, progs)
    # find, one block and stacked; mesh find
    ids = np.asarray(sorted((i, 0, 0, i) for i in range(40)), dtype=np.int32)
    lookup_ids(ids, ids[:2])
    lookup_ids_blocks([ids, ids[:20]], ids[:2])
    sharded_find_rows(mesh, [ids] * 8, ids[:2])
    # block cut
    blockcut.remap_codes_device(np.arange(10, dtype=np.int32), np.arange(10, dtype=np.int32))
    bl = ShardedBloom.for_estimated_items(16)
    tids = [bytes([i]) * 16 for i in range(1, 9)]
    blockcut.bloom_bits_device(bl.words, tids, bl.shard_bits)
    blockcut.rowgroup_minmax_device(np.arange(8, dtype=np.int32),
                                    np.arange(8, dtype=np.int32), [0, 4, 8])
    # blooms
    b1, b2 = ShardedBloom.for_estimated_items(16), ShardedBloom.for_estimated_items(16)
    b1.add_many(tids[:4])
    b2.add_many(tids[4:])
    bloom_ops.union_blooms([b1, b2])
    bloom_ops.batch_test(b1.words, b1.shard_bits, b1.n_shards, tids[:2])
    sharded_bloom_union(mesh, [b1, b2] * 4)
    # generator folds
    edges = jnp.asarray(np.asarray([0.1, 1.0], np.float32))
    reduce_ops._reduce_kernel(jnp.zeros(1024, jnp.int32), jnp.zeros(1024, jnp.float32),
                              jnp.int32(3), edges, 1024, 3)
    reduce_ops._edge_reduce_kernel(
        jnp.zeros(1024, jnp.int32), jnp.zeros(1024, jnp.float32),
        jnp.zeros(1024, jnp.float32), jnp.zeros(1024, jnp.int32), jnp.int32(3),
        edges, 1024, 3)
    # live head
    i32 = lambda n: np.zeros(n, np.int32)  # noqa: E731
    livestage._compiled_live_filter(1, 1, True, True, True, 1024, 1024, 1024)(
        i32(1024), i32(1024), i32(1024), i32(1024), i32(1024), i32(1024),
        i32(1024), i32(1024), i32(1), i32(1), np.int32(1), np.int32(2),
        np.int32(1), np.int32(4))
    livestage._compiled_find(1024)(np.zeros((1024, 4), np.int32), i32(1024),
                                   i32(4), np.int32(4))
    livestage._append_rows_device(jnp.zeros(1024, jnp.int32), jnp.zeros(16, jnp.int32), 8)
    livestage._patch_slots_device(jnp.zeros(1024, jnp.int32), jnp.zeros(4, jnp.int32),
                                  jnp.zeros(4, jnp.int32))
    # mesh search (the stacked program)
    rng = np.random.default_rng(3)
    B, S_rows, NT, R = 4, 64, 16, 8
    cols = {
        "span.trace_sid": rng.integers(0, NT, size=(B, S_rows)).astype(np.int32),
        "span.dur_us": rng.integers(0, 1000, size=(B, S_rows)).astype(np.int32),
        "span.res_idx": rng.integers(0, R, size=(B, S_rows)).astype(np.int32),
        "res.service_id": rng.integers(0, 4, size=(B, R)).astype(np.int32),
    }
    conds = (Cond(target=T_SPAN, col="span.dur_us", op="ge"),
             Cond(target=T_RES, col="res.service_id", op="eq"))
    sharded_search(mesh, ("and", ("cond", 0), ("cond", 1)), conds,
                   Operands.build([(0, 500, 0, 0.0, 0.0), (0, 2, 0, 0.0, 0.0)]),
                   cols, np.asarray([64, 50, 64, 3], dtype=np.int32), nt=NT)
    db.close()


@pytest.fixture(scope="module")
def lowered_hlo(tmp_path_factory):
    """Every module jax lowers while the scenario runs, as text with its
    debug info (jax_dump_ir_to). In-memory jit caches are dropped first:
    a kernel an earlier test already traced would not be lowered again."""
    import glob

    import jax

    d = tmp_path_factory.mktemp("ir")
    jax.clear_caches()
    with _dump_ir_to(str(d)):
        _drive_kernels()
    return [open(p).read() for p in glob.glob(str(d) + "/*.mlir")]


class _dump_ir_to:
    def __init__(self, path):
        self.path = path

    def __enter__(self):
        import jax

        self.old = jax.config.read("jax_dump_ir_to")
        jax.config.update("jax_dump_ir_to", self.path)

    def __exit__(self, *a):
        import jax

        jax.config.update("jax_dump_ir_to", self.old)


@pytest.mark.parametrize("op", _SCOPED_OPS)
def test_named_scope_in_lowered_hlo(lowered_hlo, op):
    assert any(f"tempo.{op}/" in text or f'tempo.{op}"' in text
               for text in lowered_hlo), f"no lowered module carries tempo.{op}"


def test_every_launched_op_has_its_scope():
    """Source-level net under the HLO cases: every op a TEL.launch /
    record_launch names in ops/ and parallel/ has a scoped("<op>") body in
    the same file, and scoped() keeps the function's own name (the
    benchmark groups device time by module name)."""
    import glob
    import os

    import tempo_tpu
    from tempo_tpu.ops.device import scoped

    root = os.path.dirname(tempo_tpu.__file__)
    missing = []
    for path in glob.glob(root + "/ops/*.py") + glob.glob(root + "/parallel/*.py"):
        src = open(path).read()
        ops = set(re.findall(r'TEL\.(?:launch|record_launch)\(\s*"(\w+)"', src))
        scopes = set(re.findall(r'scoped\("(\w+)"\)', src))
        missing += [(os.path.basename(path), op) for op in ops - scopes]
    assert missing == []

    def run(x):
        return x

    assert scoped("filter")(run).__name__ == "run"
