"""Chaos plane (PR 14): deterministic fault injection across every
IO/device seam, plus the resilience armor it forces -- per-query retry
budgets, the backend circuit breaker with half-open recovery, deadline
propagation, jittered worker backoff, and hedge telemetry.

The acceptance matrix lives here too:
  (a) transient backend 5xx -- masked (availability SLO ok, retry
      counters show the absorption);
  (b) sustained backend partition -- burn-rate verdict flips within one
      evaluation window, the breaker opens, then half-open-recovers
      after the rule expires;
  (c) faults-off differential -- an armed-but-empty plane is
      bit-identical to an unarmed process, with zero added launches.
"""

import json
import os
import time
import urllib.request

import pytest

from tempo_tpu.backend import MemBackend
from tempo_tpu.backend.base import BackendError
from tempo_tpu.chaos import ChaosBackend, plane
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.db.search import SearchRequest, response_to_dict
from tempo_tpu.util import breaker as breaker_mod
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.testdata import make_traces
from tempo_tpu.wire import otlp_json

TENANT = "single-tenant"


def _db(tmp_path, backend=None, name="wal"):
    cfg = TempoDBConfig(wal_path=str(tmp_path / name))
    return TempoDB(cfg, backend=backend or MemBackend())


# ------------------------------------------------------------ the plane


def test_rule_parsing_validation_and_spec_forms(tmp_path):
    rules, seed = plane.parse_rules(
        {"seed": 9, "rules": [{"site": "backend.*", "action": "latency"}]})
    assert seed == 9 and rules[0].site == "backend.*"
    with pytest.raises(ValueError):
        plane.parse_rules([{"site": "no.such.site"}])
    with pytest.raises(ValueError):
        plane.parse_rules([{"site": "backend.read", "action": "explode"}])
    with pytest.raises(ValueError):
        plane.parse_rules([{"site": "backend.read", "frobnicate": 1}])
    with pytest.raises(ValueError):
        plane.parse_rules([{"site": "backend.read", "p": 1.5}])
    # data-shaped actions must be able to reach a capable site: a rule
    # that could only ever no-op is a lying drill, rejected up front
    with pytest.raises(ValueError):
        plane.parse_rules([{"site": "backend.write", "action": "corrupt"}])
    with pytest.raises(ValueError):
        plane.parse_rules([{"site": "backend.read", "action": "drop"}])

    # spec forms: inline JSON and a rules file path
    p = plane.configure_spec('[{"site": "wal.fsync", "action": "error"}]')
    assert p.rules[0].site == "wal.fsync"
    f = tmp_path / "rules.json"
    f.write_text(json.dumps({"seed": 3, "rules": [
        {"site": "gossip.sync", "action": "drop"}]}))
    p = plane.configure_spec(str(f))
    assert p.seed == 3 and p.rules[0].action == "drop"
    plane.clear()
    assert not plane.is_active()


def test_env_activation(monkeypatch):
    monkeypatch.setenv(plane.ENV, '[{"site": "backend.read", '
                                  '"action": "error"}]')
    plane.reset_for_tests()  # forget the lazy env check
    assert plane.is_active()
    assert plane.status()["enabled"]
    plane.reset_for_tests()


def test_seeded_replay_is_byte_identical():
    rules = [{"site": "backend.read", "action": "error", "p": 0.3}]

    def run(seed):
        plane.configure(rules, seed=seed)
        be = ChaosBackend(MemBackend())
        be.inner.write("t", "b", "o", b"payload")
        for _ in range(200):
            try:
                be.read("t", "b", "o")
            except BackendError:
                pass
        return plane.active().injection_log()

    log1 = run(7)
    log2 = run(7)
    assert log1 == log2 and len(log1) > 20  # replay is exact
    log3 = run(8)
    assert log3 != log1  # the seed is the stream


def test_backend_seam_actions():
    be = ChaosBackend(MemBackend())
    be.inner.write("t", "b", "data.vtpu", b"0123456789")

    plane.configure([{"site": "backend.read_range", "action": "truncate",
                      "frac": 0.5}])
    assert be.read_range("t", "b", "data.vtpu", 0, 10) == b"01234"

    plane.configure([{"site": "backend.read", "action": "corrupt"}])
    corrupted = be.read("t", "b", "data.vtpu")
    assert corrupted != b"0123456789" and len(corrupted) == 10

    plane.configure([{"site": "backend.read", "action": "latency",
                      "latency_s": 0.05}])
    t0 = time.perf_counter()
    assert be.read("t", "b", "data.vtpu") == b"0123456789"
    assert time.perf_counter() - t0 >= 0.05

    # nth trigger: exactly every 2nd call errors
    plane.configure([{"site": "backend.read", "action": "error", "nth": 2}])
    outcomes = []
    for _ in range(6):
        try:
            be.read("t", "b", "data.vtpu")
            outcomes.append("ok")
        except BackendError:
            outcomes.append("err")
    assert outcomes == ["ok", "err"] * 3

    # injected-fault telemetry reached the kerneltel exposition
    lines = TEL.metrics_lines()
    assert any("tempo_chaos_injected_total" in ln for ln in lines)
    st = plane.status()
    assert st["injected_total"] >= 3 and st["recent_injections"]

    # drop on a write seam = the write is silently LOST
    plane.configure([{"site": "backend.write", "action": "drop"}])
    be.write("t", "b", "ghost", b"never lands")
    plane.clear()
    from tempo_tpu.backend.base import DoesNotExist

    with pytest.raises(DoesNotExist):
        be.read("t", "b", "ghost")


def test_wal_torn_append_and_fsync_fault(tmp_path):
    from tempo_tpu.db.wal import WAL
    from tempo_tpu.wire import segment

    # 3rd append torn mid-record: replay must truncate it away cleanly
    plane.configure([{"site": "wal.append", "action": "truncate",
                      "nth": 3, "frac": 0.4}])
    wal = WAL(str(tmp_path))
    blk = wal.new_block("t1")
    for tid, t in make_traces(3, seed=2):
        blk.append(tid, 1, 2, segment.segment_for_write(t, 1, 2))
    blk.close()
    plane.clear()
    replayed = wal.rescan_blocks()
    assert not replayed[0].clean
    assert len(replayed[0].records) == 2

    # fsync fault: the stable write fails loudly, not silently
    plane.configure([{"site": "wal.fsync", "action": "error"}])
    blk2 = wal.new_block("t2")
    tid, t = make_traces(1, seed=3)[0]
    blk2.append(tid, 1, 2, segment.segment_for_write(t, 1, 2))
    with pytest.raises(OSError):
        blk2.flush(sync=True)
    plane.clear()


def test_gossip_partition_and_heal():
    from tempo_tpu.ring.ring import InstanceDesc, InstanceState
    from tempo_tpu.transport.gossip import GossipKV

    a = GossipKV("127.0.0.1:0", interval_s=3600)
    b = GossipKV("127.0.0.1:0", seeds=[a.addr], interval_s=3600)
    try:
        a.update("ring", InstanceDesc(
            instance_id="i1", addr="x", state=InstanceState.ACTIVE,
            tokens=[1], heartbeat_ts=time.time()))
        # partition b -> a: outbound syncs to a's addr are dropped
        plane.configure([{"site": "gossip.sync", "action": "drop",
                          "key": a.addr}])
        assert b.sync_once(a.addr) is False
        assert "i1" not in b.get_all("ring")
        # heal: the same sync converges in one round trip
        plane.clear()
        assert b.sync_once(a.addr) is True
        assert "i1" in b.get_all("ring")
    finally:
        a.close()
        b.close()


def test_device_launch_shim():
    from tempo_tpu.chaos.plane import ChaosCompileError, ChaosDeviceOOM

    plane.configure([{"site": "device.launch", "action": "error",
                      "error": "compile_failure", "key": "filter"}])
    with pytest.raises(ChaosCompileError):
        TEL.record_launch("filter", ("chaos-shim-test", 1), 1024)
    # other ops untouched (key match)
    assert isinstance(TEL.record_launch("reduce", ("chaos-shim-test", 2),
                                        1024), bool)
    plane.configure([{"site": "device.launch", "action": "error",
                      "error": "device_oom"}])
    with pytest.raises(ChaosDeviceOOM):
        TEL.record_launch("filter", ("chaos-shim-test", 3), 1024)
    plane.clear()
    assert isinstance(TEL.record_launch("filter", ("chaos-shim-test", 4),
                                        1024), bool)


def test_rpc_client_tap():
    from tempo_tpu.transport.client import HTTPIngesterClient, TransportError

    c = HTTPIngesterClient("http://127.0.0.1:1")  # nothing listens
    plane.configure([{"site": "rpc.client", "action": "drop"}])
    with pytest.raises(TransportError) as ei:
        c.search("t", SearchRequest(tags={"a": "b"}))
    assert "black-holed" in str(ei.value)
    plane.clear()


# ----------------------------------------------- faults-off differential


def _build_store(tmp_path, name):
    db = _db(tmp_path, name=f"wal-{name}")
    db.cfg.compaction.min_input_blocks = 2
    all_traces = make_traces(24, seed=12, n_spans=5)
    db.write_block(TENANT, all_traces[:12])
    db.write_block(TENANT, all_traces[12:])
    return db, all_traces


def _exercise(db, all_traces):
    """search + find + compact; returns (wire-comparable outputs,
    launches)."""
    TEL.reset()
    l0 = TEL.launch_count()
    req = SearchRequest(tags={"service.name": "db"}, limit=10)
    resp1 = response_to_dict(db.search(TENANT, req))
    db.compact_once(TENANT)
    db.poll_now()
    resp2 = response_to_dict(db.search(TENANT, req))
    tid, _ = all_traces[3]
    found = db.find_trace_by_id(TENANT, tid)
    return (resp1, resp2, otlp_json.dumps(found),
            TEL.launch_count() - l0)


def test_faults_off_differential_bit_identical(tmp_path):
    """Acceptance (c): an ARMED process with no matching rules produces
    byte-identical outputs to an unarmed one, at the same launch count
    -- the taps are provably free when idle."""
    plane.clear()  # unarmed leg (taps are `is None` checks)
    db1, traces1 = _build_store(tmp_path, "off")
    out_off = _exercise(db1, traces1)
    db1.close()

    # armed leg: plane active (backend wrapper interposed) but no rule
    # matches anything this run touches
    plane.configure([{"site": "gossip.sync", "action": "drop",
                      "key": "10.255.255.1:*"}], seed=1)
    db2, traces2 = _build_store(tmp_path, "on")
    assert isinstance(db2.backend, ChaosBackend)
    out_on = _exercise(db2, traces2)
    db2.close()
    plane.clear()

    assert out_off[:3] == out_on[:3]  # bit-identical outputs
    assert out_off[3] == out_on[3]  # zero added launches
    assert plane.status()["enabled"] is False


# --------------------------------------------------- resilience hardening


def _frontend(tmp_path, **kw):
    from tempo_tpu.services.frontend import Frontend
    from tempo_tpu.services.querier import Querier

    db = _db(tmp_path, name=f"wal-fe-{len(os.listdir(tmp_path))}")
    q = Querier(db, None, lambda addr: None, workers=2)
    fe = Frontend(q, n_workers=kw.pop("n_workers", 2),
                  hedge_after_s=kw.pop("hedge_after_s", 0.0), **kw)
    return fe, db


def test_retry_budget_caps_the_storm(tmp_path, monkeypatch):
    """A dying backend used to cost jobs x MAX_RETRIES extra load; the
    per-query budget makes the worst case additive."""
    from tempo_tpu.services.frontend import _Job

    monkeypatch.setenv("TEMPO_RETRY_BUDGET", "2")
    fe, db = _frontend(tmp_path)
    try:
        calls = {"n": 0}

        def boom():
            calls["n"] += 1
            raise BackendError("down")

        jobs = [_Job(kind="search_blocks", payload={}, fn=boom, args=())
                for _ in range(4)]
        fe._run_jobs("t", jobs, timeout=10.0)
        assert all(j.error is not None for j in jobs)
        # 4 first tries + exactly the budgeted 2 retries
        assert calls["n"] == 6
        st = TEL.retry_stats()
        assert st.get("retry") == 2
        assert st.get("budget_exhausted", 0) >= 1
        assert "retries" in TEL.snapshot()
    finally:
        fe.stop()
        db.close()


def test_hedge_telemetry_win(tmp_path):
    """A stuck job's hedge twin wins: tempo_hedge_total{outcome="win"}
    ticks and the job span carries the outcome."""
    from tempo_tpu.services.frontend import _Job

    fe, db = _frontend(tmp_path, hedge_after_s=0.05)
    try:
        # a second cache domain (a querier that has polled): a twin has
        # somewhere to go other than the original's worker, as in a tree
        assert fe.poll_job(wait_s=0.0, worker_id="querier-1") is None
        state = {"calls": 0}

        def slow_then_fast():
            state["calls"] += 1
            if state["calls"] == 1:
                time.sleep(1.0)  # the stuck original
            return "r"

        job = _Job(kind="search_blocks", payload={}, fn=slow_then_fast,
                   args=())
        fe._run_jobs("t", [job], timeout=10.0)
        assert job.result == "r" and job.error is None
        assert job.hedged and job.hedge_outcome == "win"
        assert TEL.hedge_stats().get("win", 0) >= 1
        assert "hedging" in TEL.snapshot()
        assert any("tempo_hedge_total" in ln for ln in TEL.metrics_lines())
    finally:
        fe.stop()
        db.close()


@pytest.mark.parametrize("domains", ["one_process", "two_workers"])
def test_a_twin_needs_another_worker_and_a_job_in_hand(tmp_path, domains):
    """The hedge clock runs from the hand-off, not from the enqueue, and a
    twin is enqueued only where a worker other than the original's could
    take it: in a one-process frontend a job older than hedge_after_s gets
    no twin while it is queued behind another and none while its only
    worker runs it; with a second cache domain it gets one, once its
    worker has had it for hedge_after_s."""
    from tempo_tpu.services.frontend import _Job

    fe, db = _frontend(tmp_path, n_workers=1, hedge_after_s=0.1)
    try:
        if domains == "two_workers":
            assert fe.poll_job(wait_s=0.0, worker_id="querier-1") is None
        before = sum(TEL.hedge_stats().values())
        seen = {}

        def first():
            time.sleep(0.5)  # holds the one worker: `second` waits queued
            seen["second_queued"] = (second.hedged, second.handed_wall)
            return "a"

        def work():
            time.sleep(0.5)
            return "b"

        head = _Job(kind="search_blocks", payload={}, fn=first, args=())
        second = _Job(kind="search_blocks", payload={}, fn=work, args=())
        fe._run_jobs("t", [head, second], timeout=10.0)
        assert (head.result, second.result) == ("a", "b")
        # 0.5 s in the queue, five times hedge_after_s: no twin, either way
        assert seen["second_queued"] == (False, 0.0)
        assert second.exec_seq == 1  # nothing ran it a second time
        hedges = sum(TEL.hedge_stats().values()) - before
        if domains == "one_process":
            assert not head.hedged and not second.hedged and hedges == 0
        else:
            assert head.hedged and second.hedged and hedges == 2
            assert second.hedge_outcome == "unneeded"  # the original won
    finally:
        fe.stop()
        db.close()


def test_deadline_skips_local_execution(tmp_path):
    from tempo_tpu.services.frontend import _Job

    fe, db = _frontend(tmp_path, n_workers=0)
    try:
        ran = {"n": 0}

        def fn():
            ran["n"] += 1

        job = _Job(kind="search_blocks", payload={}, fn=fn, args=())
        job.deadline_unix = time.time() - 1.0
        fe._execute_one("t", job)
        assert ran["n"] == 0 and job.cancelled and job.done.is_set()
        # the skip surfaces as a shard TIMEOUT, never a silent partial
        # (find/metrics raise on it; search degrades)
        assert isinstance(job.error, TimeoutError)
    finally:
        fe.stop()
        db.close()


def test_deadline_rides_wire_job_and_worker_skips(tmp_path):
    """The frontend stamps the caller deadline on pulled wire jobs; a
    worker that receives an already-dead job posts a non-retryable
    deadline error instead of scanning."""
    from tempo_tpu.services import worker as worker_mod
    from tempo_tpu.services.frontend import _Job

    fe, db = _frontend(tmp_path, n_workers=0)
    try:
        job = _Job(kind="search_blocks", payload={"block_ids": []},
                   fn=lambda: None, args=())
        job.deadline_unix = time.time() + 30.0
        fe.queue.enqueue("t", job)
        wire = fe.poll_job(wait_s=1.0, worker_id="w1")
        # RELATIVE remaining budget on the wire (clock-skew immune)
        assert wire and wire["deadline_in_s"] == pytest.approx(30.0,
                                                               abs=2.0)

        # worker side: a stub frontend hands out a job whose deadline
        # already passed; execute_job must never run
        executed = {"n": 0}
        posted = []

        w = worker_mod.QuerierWorker.__new__(worker_mod.QuerierWorker)
        w.querier = None
        w.token = ""
        w.poll_wait_s = 0.01
        w.worker_id = "w-dead"
        w.jobs_executed = w.jobs_failed = 0
        import threading

        w._stop = threading.Event()
        dead_job = {"id": "j1", "kind": "search_blocks", "tenant": "t",
                    "payload": {}, "deadline_in_s": -5.0}

        def fake_post(addr, path, payload, timeout):
            posted.append((path, payload))
            if path == "/internal/jobs/poll":
                if len(posted) > 1:
                    w._stop.set()
                return dict(dead_job)
            return {}

        w._post = fake_post
        monkey_exec = worker_mod.execute_job

        def counting_exec(*a, **k):
            executed["n"] += 1
            return monkey_exec(*a, **k)

        worker_mod.execute_job = counting_exec
        try:
            w._loop("http://stub")
        finally:
            worker_mod.execute_job = monkey_exec
        results = [p for path, p in posted if path == "/internal/jobs/result"]
        assert executed["n"] == 0
        assert results and results[0]["ok"] is False
        assert "deadline" in results[0]["error"]
        assert results[0]["retryable"] is False
    finally:
        fe.stop()
        db.close()


def test_worker_backoff_is_jittered_exponential(monkeypatch):
    """Frontend down: poll failures back off exponentially (capped) and
    a successful poll resets the clock -- no 1 Hz thundering herd."""
    import random as random_mod
    import threading

    from tempo_tpu.services.worker import QuerierWorker

    monkeypatch.setattr(random_mod, "random", lambda: 1.0)  # kill jitter
    w = QuerierWorker.__new__(QuerierWorker)
    w.querier = None
    w.token = ""
    w.poll_wait_s = 0.01
    w.worker_id = "w-flap"
    w.jobs_executed = w.jobs_failed = 0

    waits = []
    fails = {"n": 0}

    class FakeStop:
        def is_set(self):
            return len(waits) >= 8

        def wait(self, t):
            waits.append(t)
            return False

    w._stop = FakeStop()

    def flapping_post(addr, path, payload, timeout):
        fails["n"] += 1
        if fails["n"] == 6:  # one successful poll mid-flap
            return None  # empty poll = success, resets backoff
        raise OSError("connection refused")

    w._post = flapping_post
    w._loop("http://flap")
    # 0.5 1 2 4 5 (cap) ... then reset after the success ... 0.5 1 ...
    assert waits[:5] == [0.5, 1.0, 2.0, 4.0, 5.0]
    assert waits[5:7] == [0.5, 1.0]  # the reset after one good poll


def test_ingester_leg_breaker_sheds_and_reports(tmp_path):
    """A remote ingester leg that keeps failing is shed fast (degraded
    coverage, like the existing failed-leg tolerance) and shows up in
    the breaker registry."""
    from tempo_tpu.ring.ring import InMemoryKV, Lifecycler, Ring
    from tempo_tpu.services.querier import Querier

    kv = InMemoryKV()
    lc = Lifecycler(kv, "ingester-ring", "remote-1",
                    addr="http://127.0.0.1:1")  # nothing listens
    lc.start()
    db = _db(tmp_path, name="wal-leg")
    from tempo_tpu.transport.client import HTTPIngesterClient

    q = Querier(db, Ring(kv, "ingester-ring"),
                lambda addr: HTTPIngesterClient(addr, timeout=0.2),
                workers=2)
    try:
        br = breaker_mod.get_breaker("ingester:http://127.0.0.1:1",
                                     min_volume=3, error_rate=0.5,
                                     open_s=60.0, window_s=60.0)
        for _ in range(4):
            q.search_recent("t", SearchRequest(tags={"a": "b"}))
        assert br.state == "open"
        # open leg: search_recent still answers (degraded, shed fast)
        t0 = time.perf_counter()
        q.search_recent("t", SearchRequest(tags={"a": "b"}))
        assert time.perf_counter() - t0 < 0.15  # no timeout paid
        assert "ingester:http://127.0.0.1:1" in breaker_mod.breakers_snapshot()
    finally:
        db.close()


# ------------------------------------------------------ acceptance matrix


def _mk_app(tmp_path, **cfg_kw):
    import socket

    from tempo_tpu.services.app import App, AppConfig
    from tempo_tpu.services.ingester import IngesterConfig

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    cfg = AppConfig(storage_path=str(tmp_path / "store"), http_port=port,
                    compaction_cycle_s=9999,
                    ingester=IngesterConfig(flush_check_period_s=9999),
                    **cfg_kw)
    app = App(cfg)
    app.start()
    app.serve_http(background=True)
    return app, f"http://127.0.0.1:{port}"


def _seed_blocks(app, n=24):
    traces = make_traces(n, seed=21, n_spans=4)
    app.db.write_block(TENANT, traces[: n // 2])
    app.db.write_block(TENANT, traces[n // 2:])
    app.db.poll_now()
    return traces


def _drop_reader_caches(app):
    with app.db._cache_lock:
        app.db._block_cache.clear()


def test_matrix_a_transient_faults_masked(tmp_path, monkeypatch):
    """Acceptance (a): 5%-ish backend 5xx on data reads -- queries keep
    succeeding (retries + shard degradation absorb the faults), the
    read-availability SLO stays ok, and the retry/injection counters
    prove faults actually flowed."""
    monkeypatch.setenv("TEMPO_RETRY_BUDGET", "64")
    # the drill repeats one query to force backend reads; the result
    # cache would serve the repeats without touching the backend
    monkeypatch.setenv("TEMPO_RESULT_CACHE", "0")
    plane.configure([], seed=5)  # arm BEFORE the app builds its backend
    app, base = _mk_app(tmp_path)
    try:
        _seed_blocks(app)
        app.slo.evaluate()  # baseline SLO sample
        plane.configure(
            [{"site": "backend.read*", "action": "error", "p": 0.05,
              "key": "*/data.vtpu"}], seed=5)
        req = SearchRequest(tags={"service.name": "db"}, limit=10)
        for _ in range(12):
            _drop_reader_caches(app)
            resp = app.frontend.search(TENANT, req)
            assert resp is not None  # degraded-at-worst, never an error
        plane_status = plane.status()
        st = TEL.retry_stats()
        slo = app.slo.evaluate()
        assert plane_status["injected_total"] > 0
        assert st.get("retry", 0) > 0  # the masking, visible
        av = slo["objectives"]["read-availability"]
        assert av["verdict"] == "ok", av
        assert av["bad_total"] == 0
        # the whole surface is served over HTTP too
        chaos_http = json.load(urllib.request.urlopen(
            base + "/status/chaos", timeout=10))
        assert chaos_http["enabled"] and chaos_http["injected_total"] > 0
        assert "breakers" in chaos_http and "retries" in chaos_http
    finally:
        plane.clear()
        app.stop()


def test_matrix_b_partition_trips_breaker_then_recovers(tmp_path,
                                                        monkeypatch):
    """Acceptance (b): a sustained backend partition flips the
    burn-rate verdict within one evaluation window and opens the
    circuit breaker; when the rule expires, half-open probes close it
    and reads succeed again."""
    monkeypatch.setenv("TEMPO_BREAKER_MIN_VOLUME", "4")
    monkeypatch.setenv("TEMPO_BREAKER_OPEN_S", "0.3")
    monkeypatch.setenv("TEMPO_BREAKER_PROBES", "1")
    # the drill repeats one by-id lookup to drive the breaker; the
    # result cache would serve the repeats without touching the backend
    monkeypatch.setenv("TEMPO_RESULT_CACHE", "0")
    plane.configure([], seed=2)
    app, _base = _mk_app(tmp_path)
    try:
        traces = _seed_blocks(app)
        app.slo.evaluate()  # window-opening sample, everything green
        tid = traces[2][0]
        assert app.frontend.find_trace_by_id(TENANT, tid) is not None

        # ---- the partition: every backend read fails for ~1.2 s
        plane.configure([{"site": "backend.read*", "action": "error",
                          "for_s": 1.2}], seed=2)
        req = SearchRequest(tags={"service.name": "db"}, limit=10)
        for _ in range(3):
            _drop_reader_caches(app)
            app.frontend.search(TENANT, req)  # shards fail -> breaker food
        errors = 0
        for _ in range(4):
            _drop_reader_caches(app)
            try:
                app.frontend.find_trace_by_id(TENANT, tid)
            except Exception:
                errors += 1
        assert errors >= 1
        br = app.frontend.backend_breaker
        assert br.state == "open", br.snapshot()
        slo = app.slo.evaluate()  # ONE evaluation window later
        av = slo["objectives"]["read-availability"]
        assert av["verdict"] == "critical", av
        assert av["burn_rates"]["5m"] > 14.4

        # ---- the rule expires; half-open probes must recover the leg
        time.sleep(1.4)  # past for_s AND past open_s
        _drop_reader_caches(app)
        for _ in range(4):
            app.frontend.search(TENANT, req)  # probe traffic
            if br.state == "closed":
                break
        assert br.state == "closed", br.snapshot()
        to_states = [t["to"] for t in br.snapshot()["transitions"]]
        assert to_states[-3:] == ["open", "half_open", "closed"] or \
            to_states[-2:] == ["half_open", "closed"], to_states
        got = app.frontend.find_trace_by_id(TENANT, tid)
        assert got is not None  # the read path healed
        assert any("tempo_circuit_breaker_state" in ln
                   for ln in TEL.metrics_lines())
    finally:
        plane.clear()
        app.stop()


def test_vulture_under_chaos_stays_green(tmp_path, monkeypatch):
    """The PR-11 loop closed: the continuous-verification prober runs a
    full cycle WHILE transient faults are being injected into the
    backend data path -- every probe family still passes (the armor
    masks the faults), and the injection counters prove chaos was
    live."""
    from tempo_tpu.vulture import Vulture, VultureConfig

    monkeypatch.setenv("TEMPO_RETRY_BUDGET", "64")
    plane.configure([], seed=11)
    app, base = _mk_app(tmp_path)
    try:
        _seed_blocks(app)  # flushed blocks for search coverage
        plane.configure(
            [{"site": "backend.read*", "action": "error", "p": 0.04,
              "key": "*/data.vtpu"}], seed=11)
        v = Vulture(VultureConfig(
            push_url=base, query_url=base, visibility_timeout_s=10.0,
            retry_interval_s=0.05, spans_per_trace=3, batch_ids=3,
            flush_every=0, seed=4))  # live families; cold probes use an
        # unretried fresh reader by design and get their own matrix legs
        results = v.cycle()
        assert Vulture.ok(results), [
            (r.family, r.outcome, r.detail) for r in results
            if r.outcome != "ok"]
        assert v.status()["slo"]["verdict"] == "ok"
    finally:
        plane.clear()
        app.stop()


def test_soak_chaos_flag_reports_injections(tmp_path, monkeypatch):
    """soak --chaos against an in-process armed app: the run stays ok
    and the report carries the injection/retry evidence."""
    import io
    from contextlib import redirect_stdout

    import soak

    monkeypatch.setenv("TEMPO_RETRY_BUDGET", "64")
    plane.configure([], seed=1)
    app, base = _mk_app(tmp_path)
    try:
        _seed_blocks(app)
        # the default spec's shape, key-restricted to data objects so
        # the UNRETRIED fresh-reader legs (bloom probes of unrelated
        # blocks) stay deterministic inside tier-1
        plane.configure(
            [{"site": "backend.read*", "action": "error", "p": 0.05,
              "key": "*/data.vtpu"},
             {"site": "rpc.client", "action": "latency",
              "latency_s": 0.005, "p": 0.1}], seed=1)
        # reader-cache churn so soak searches keep paying backend reads
        # (a warm block cache would serve the whole soak injection-free)
        import threading

        stop_churn = threading.Event()

        def churn():
            while not stop_churn.wait(0.2):
                _drop_reader_caches(app)

        churner = threading.Thread(target=churn, daemon=True)
        churner.start()
        buf = io.StringIO()
        try:
            with redirect_stdout(buf):
                rc = soak.main(["--target", base, "--duration", "3",
                                "--writers", "1", "--readers", "1",
                                "--chaos"])
        finally:
            stop_churn.set()
            churner.join(timeout=5)
        report = json.loads(buf.getvalue())
        assert rc == 0, report
        assert report["ok"]
        assert report["chaos"]["enabled"]
        assert report["chaos"]["injected_total"] > 0
    finally:
        plane.clear()
        app.stop()


# ------------------------------------------------------- runtime control


def test_internal_chaos_endpoint_and_cli(tmp_path, capsys):
    """POST /internal/chaos swaps rules at runtime; the CLI validates a
    rules file, lists sites, and injects/clears against a live app."""
    from tempo_tpu.cli.__main__ import main as cli_main

    plane.configure([], seed=0)  # armed, empty
    app, base = _mk_app(tmp_path)
    try:
        # CLI: sites + validate
        cli_main(["chaos", "sites"])
        out = capsys.readouterr().out
        assert "backend.read" in out and "device.launch" in out
        rules_file = tmp_path / "rules.json"
        rules_file.write_text(json.dumps({"seed": 6, "rules": [
            {"site": "rpc.client", "action": "latency",
             "latency_s": 0.01}]}))
        cli_main(["chaos", "validate", str(rules_file)])
        assert json.loads(capsys.readouterr().out)["seed"] == 6
        bad = tmp_path / "bad.json"
        bad.write_text('[{"site": "nope"}]')
        with pytest.raises(SystemExit):
            cli_main(["chaos", "validate", str(bad)])
        capsys.readouterr()

        # CLI: inject against the live app, observe, clear
        cli_main(["chaos", "inject", base, "--rules", str(rules_file)])
        injected = json.loads(capsys.readouterr().out)
        assert injected["enabled"] and injected["rules"][0]["site"] == "rpc.client"
        assert plane.is_active() and plane.active().seed == 6
        cli_main(["chaos", "status", base])
        assert json.loads(capsys.readouterr().out)["enabled"]
        cli_main(["chaos", "inject", base, "--clear"])
        assert json.loads(capsys.readouterr().out)["enabled"] is False
        assert not plane.is_active()

        # bad rules 400 at the endpoint
        import urllib.error

        req = urllib.request.Request(
            base + "/internal/chaos",
            data=json.dumps({"rules": [{"site": "nope"}]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
    finally:
        plane.clear()
        app.stop()


# ------------------------------------------------------------ AOT warmup


def test_warmup_corpus_and_run(tmp_path):
    """First compiles land in the CostLedger corpus; run_warmup replays
    it through the canonical builders (and the app flag surfaces the
    report)."""
    from tempo_tpu.util import costledger, warmup

    led_path = str(tmp_path / "ledger.json")
    costledger.configure(led_path)
    warmup.reset_for_tests()
    try:
        # a real first compile records its (op, bucket) pair durably
        TEL.reset()
        warmup._warm_filter(1024)
        pairs = warmup.corpus()
        assert ["filter", "1024"] in [list(p) for p in pairs], pairs
        on_disk = json.loads(open(led_path).read())
        assert on_disk["entries"]["compile_corpus"]["pairs"]

        # replaying the corpus compiles without error and reports it
        report = warmup.run_warmup()
        assert ["filter", "1024"] in report["warmed"]
        assert not report["errors"]
    finally:
        costledger.reset_for_tests()
        warmup.reset_for_tests()


def test_warmup_app_flag(tmp_path, monkeypatch):
    """--warmup.shapes: the app compiles the corpus before serving and
    /status/chaos carries the report."""
    from tempo_tpu.util import costledger, warmup

    # the env pin keeps App.__init__ from repointing the ledger at
    # <storage>/cost_ledger.json (operator-aimed env wins by contract)
    monkeypatch.setenv(costledger.LEDGER_ENV, str(tmp_path / "ledger.json"))
    costledger.configure(str(tmp_path / "ledger.json"))
    warmup.reset_for_tests()
    costledger.ledger().update(warmup.CORPUS_KEY,
                               pairs=[["filter", "1024"], ["nosuch", "64"]])
    app, base = _mk_app(tmp_path, warmup_shapes=True)
    try:
        assert app.warmup_report is not None
        assert ["filter", "1024"] in app.warmup_report["warmed"]
        assert ["nosuch", "64"] in app.warmup_report["skipped"]
        st = json.load(urllib.request.urlopen(base + "/status/chaos",
                                              timeout=10))
        assert st["warmup"]["warmed"]
    finally:
        app.stop()
        costledger.reset_for_tests()
        warmup.reset_for_tests()
