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


_STAGE_CASES = {
    "counter_only": _case_counter_only, "nesting": _case_nesting,
    "verify_leaf": _case_verify_leaf, "late_attrs": _case_late_attrs,
    "families": _case_families, "launch": _case_launch,
    "raises": _case_raises, "annotation": _case_annotation,
    "uncounted": _case_uncounted, "miss_reason": _case_miss_reason,
}


@pytest.mark.parametrize("case", sorted(_STAGE_CASES))
def test_stage(case):
    _STAGE_CASES[case]()


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
