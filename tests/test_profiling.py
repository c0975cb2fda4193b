"""Continuous profiling plane (util/profiler + util/log +
util/runtimestats + the kerneltel/app wiring).

Covers the acceptance surface: sampler attribution (a busy tempo_tpu
component dominates its label and samples tag to the active query's
self-trace id), the profiling-off differential (bit-identical search
results, unchanged launch counts), slow-query auto-capture linking a
folded artifact into the slow-query log, folded-output parseability,
TimedLock/TimedRLock passthrough semantics, artifact-store bounds +
atomicity + path hygiene, the structured log shim, runtime health
gauges, strict OpenMetrics parse of every new family, and the e2e
loop: chaos slow-launch -> slow-query log entry carrying BOTH a
self-trace id and a profile artifact id -> `tempo-tpu-cli profile`
renders the artifact.
"""

from __future__ import annotations

import json
import socket
import threading
import time
import types
import urllib.parse
import urllib.request

import pytest

from tempo_tpu.util import log as logmod
from tempo_tpu.util import profiler as profmod
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.profiler import (
    PROF,
    ArtifactStore,
    TimedLock,
    TimedRLock,
    timed_lock,
    timed_rlock,
)

TENANT = "prof-t"


@pytest.fixture(autouse=True)
def _fresh_profiler():
    PROF.stop()
    PROF.reset()
    TEL.reset()
    yield
    PROF.stop()
    PROF.reset()
    TEL.reset()


def _busy_thread(stop: threading.Event, trace=None):
    """Spin inside tempo_tpu code (util/testdata -> wire/model) so the
    sampler has a real component to attribute."""
    from tempo_tpu.util.testdata import make_traces

    def run():
        token = TEL.set_active_trace(trace) if trace is not None else None
        try:
            while not stop.is_set():
                make_traces(2, seed=3, n_spans=2)
        finally:
            if token is not None:
                TEL.reset_active_trace(token)

    t = threading.Thread(target=run, daemon=True, name="prof-busy")
    t.start()
    return t


# ----------------------------------------------------------- attribution


def test_sampler_attribution_component_and_query():
    """A busy tempo_tpu component dominates its sample label, and ring
    samples from the busy thread carry the parked trace's id."""
    fake = types.SimpleNamespace(trace_id=b"\xab" * 16)
    PROF.start(hz=250.0)
    stop = threading.Event()
    t = _busy_thread(stop, trace=fake)
    time.sleep(0.6)
    stop.set()
    t.join(timeout=5)
    snap = PROF.status_snapshot()
    PROF.stop()
    s = snap["sampler"]
    assert s["running"] and s["samples_total"] > 10
    assert s["top_stacks"], "no folded stacks aggregated"
    # the busy thread lives in util/testdata + wire/model: its
    # component labels accumulate samples (other tests' parked daemon
    # threads also sample into THEIR components, so the comparison
    # below is within this query's tagged samples, not process-wide)
    comps = s["components"]
    busy = sum(n for c, n in comps.items() if c in ("testdata", "wire"))
    assert busy > 0
    # query attribution: ring samples from the busy thread tag the
    # parked trace id (kerneltel set_active_trace -> thread registry),
    # and the busy component dominates within that query's samples
    want = fake.trace_id.hex()
    with PROF._lock:
        tagged = [r for r in PROF._ring if r[1] == want]
    assert tagged, "no ring samples attributed to the active query"
    in_busy = sum(1 for r in tagged if r[2] in ("testdata", "wire"))
    assert in_busy > 0.8 * len(tagged), (in_busy, len(tagged))


def test_folded_output_parses():
    PROF.start(hz=250.0)
    stop = threading.Event()
    t = _busy_thread(stop)
    time.sleep(0.4)
    stop.set()
    t.join(timeout=5)
    folded = PROF.folded()
    PROF.stop()
    assert folded.strip()
    for line in folded.splitlines():
        stack, _, count = line.rpartition(" ")
        assert int(count) >= 1
        frames = stack.split(";")
        assert len(frames) >= 2  # component root + at least one frame
        assert all(f for f in frames)
    # burst capture (the /debug/profile body) parses the same way
    stop2 = threading.Event()
    t2 = _busy_thread(stop2)
    out = PROF.sample_cpu(0.2, hz=300.0, fmt="folded")
    stop2.set()
    t2.join(timeout=5)
    assert out.strip()
    for line in out.splitlines():
        stack, _, count = line.rpartition(" ")
        assert int(count) >= 1 and ";" in stack
    text = PROF.sample_cpu(0.1, hz=200.0, fmt="text")
    assert "sampling profile" in text


# ------------------------------------------------ profiling-off differential


def test_profiling_off_differential_bit_identical(tmp_path):
    """Sampler on vs off: search results bit-identical, launch counts
    unchanged; TEMPO_PROFILE_HZ=0 makes ensure_sampler a strict no-op."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest

    from tempo_tpu.util.testdata import make_traces

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "wal"),
                               device_promote_touches=1),
                 backend=MemBackend())
    db.write_block(TENANT, make_traces(40, seed=9, n_spans=5))
    metas = db.blocklist.metas(TENANT)
    req = SearchRequest(query="{ duration > 1ms }", limit=50)

    def run():
        l0 = TEL.launch_count()
        resp = db.search_blocks(TENANT, metas, req)
        return ([ (t.trace_id, json.dumps(t.to_dict(), sort_keys=True))
                  for t in resp.traces ],
                TEL.launch_count() - l0)

    run()  # warm: staging + compiles out of the differential
    base, launches_off = run()
    assert base, "search found nothing; differential is vacuous"
    PROF.start(hz=200.0)
    try:
        on, launches_on = run()
    finally:
        PROF.stop()
    again, launches_off2 = run()
    assert on == base == again
    assert launches_on == launches_off == launches_off2
    db.close()

    # hz=0 kills the always-on sampler entirely
    import os

    old = os.environ.get(profmod.PROFILE_HZ_ENV)
    os.environ[profmod.PROFILE_HZ_ENV] = "0"
    try:
        assert PROF.ensure_sampler() is False
        assert not PROF.sampling
    finally:
        if old is None:
            os.environ.pop(profmod.PROFILE_HZ_ENV, None)
        else:
            os.environ[profmod.PROFILE_HZ_ENV] = old


# ------------------------------------------------- slow-query auto-capture


def test_slow_query_auto_capture_links_artifact(tmp_path, monkeypatch):
    monkeypatch.setenv("TEMPO_SLO_SEARCH_P99_S", "0.05")
    PROF.configure_artifacts(str(tmp_path / "profiles"))
    PROF.start(hz=250.0)
    stop = threading.Event()
    fake = types.SimpleNamespace(trace_id=b"\x17" * 16)
    t = _busy_thread(stop, trace=fake)
    time.sleep(0.5)
    stop.set()
    t.join(timeout=5)
    # a fast query never captures
    TEL.record_query("search", 0.001, fake.trace_id.hex(), "fast")
    fast = [q for q in TEL.slow_queries(20) if q["detail"] == "fast"][0]
    assert fast["profile_artifact_id"] == ""
    # a slow one (past the 0.05s class threshold) captures and links
    TEL.record_query("search", 0.4, fake.trace_id.hex(), "slow")
    slow = [q for q in TEL.slow_queries(20) if q["detail"] == "slow"][0]
    aid = slow["profile_artifact_id"]
    assert aid and slow["self_trace_id"] == fake.trace_id.hex()
    data = PROF.artifact_bytes(aid)
    assert data is not None
    text = data.decode()
    assert "slow-query profile" in text
    assert f"self_trace_id={fake.trace_id.hex()}" in text
    body = [ln for ln in text.splitlines()
            if ln and not ln.startswith("#")]
    assert body, "captured window held no samples"
    for line in body:
        stack, _, count = line.rpartition(" ")
        assert int(count) >= 1 and ";" in stack
    PROF.stop()
    # sampler off -> no capture regardless of latency
    TEL.record_query("search", 9.9, "", "off")
    off = [q for q in TEL.slow_queries(20) if q["detail"] == "off"][0]
    assert off["profile_artifact_id"] == ""


# --------------------------------------------------------- timed locks


def test_timed_lock_passthrough_and_semantics(monkeypatch):
    # unarmed: the factories return RAW threading locks (zero overhead)
    monkeypatch.delenv(profmod.LOCK_PROFILE_ENV, raising=False)
    assert not isinstance(timed_lock("x"), TimedLock)
    assert type(timed_lock("x")) is type(threading.Lock())
    # armed: wrappers with full lock semantics
    monkeypatch.setenv(profmod.LOCK_PROFILE_ENV, "1")
    lk = timed_lock("test_lock")
    assert isinstance(lk, TimedLock)
    with lk:
        assert lk.locked()
        # blocking=False from another thread fails cleanly
        got = []
        t = threading.Thread(
            target=lambda: got.append(lk.acquire(blocking=False)))
        t.start()
        t.join()
        assert got == [False]
    assert not lk.locked()
    # contended acquisition is measured (and only contended ones hit
    # the wait histogram)
    lk.acquire()
    release_at = threading.Event()

    def holder_release():
        release_at.wait(5)
        lk.release()

    t = threading.Thread(target=holder_release)
    t.start()
    waiter_done = threading.Event()

    def waiter():
        lk.acquire()
        lk.release()
        waiter_done.set()

    w = threading.Thread(target=waiter)
    w.start()
    time.sleep(0.05)
    release_at.set()
    assert waiter_done.wait(5)
    t.join()
    w.join()
    stats = profmod.lock_stats()["test_lock"]
    assert stats["acquisitions"] >= 3
    assert stats["contended"] >= 1
    assert stats["wait_max_s"] >= 0.02
    # RLock recursion: re-acquire by the owner is never contention
    rl = timed_rlock("test_rlock")
    assert isinstance(rl, TimedRLock)
    with rl:
        with rl:
            assert rl._is_owned()
    assert profmod.lock_stats()["test_rlock"]["contended"] == 0
    # Condition over a TimedLock (the frontend-queue shape)
    clk = timed_lock("test_cv_lock")
    cv = threading.Condition(clk)
    hits = []

    def consumer():
        with cv:
            while not hits:
                if not cv.wait(5):
                    return

    c = threading.Thread(target=consumer)
    c.start()
    time.sleep(0.02)
    with cv:
        hits.append(1)
        cv.notify_all()
    c.join(timeout=5)
    assert not c.is_alive()


# ------------------------------------------------------- artifact store


def test_artifact_store_bounds_and_atomicity(tmp_path):
    store = ArtifactStore(str(tmp_path / "art"), max_files=3)
    ids = []
    for i in range(6):
        ids.append(store.put("slowq", f"stack {i}\n".encode(),
                             suffix=".folded"))
        time.sleep(0.01)  # distinct mtimes for deterministic pruning
    listed = store.list()
    assert len(listed) <= 3
    # newest survive, oldest pruned
    assert {a["id"] for a in listed} <= set(ids[-4:])
    newest = ids[-1]
    assert store.get(newest) == b"stack 5\n"
    assert store.get(ids[0]) is None  # pruned
    # path hygiene: traversal-shaped ids never read outside the store
    assert store.get("../art/" + newest) is None
    assert store.get("..") is None
    assert store.get(".tmp-x") is None
    # no torn temp files left behind
    import os

    assert not [n for n in os.listdir(store.root)
                if n.startswith(".tmp-")]
    # a foreign DIRECTORY in the root (under the app, the storage
    # poller drops tenant-index dirs beside the artifacts) is neither
    # listed, readable, nor pruned
    os.makedirs(os.path.join(store.root, "__tenant__"), exist_ok=True)
    assert store.get("__tenant__") is None
    assert "__tenant__" not in {a["id"] for a in store.list()}
    store.put("slowq", b"x\n", suffix=".folded")  # prune pass runs
    assert os.path.isdir(os.path.join(store.root, "__tenant__"))


# ------------------------------------------------------------- log shim


def test_log_shim_structured_and_suppressed(capsys):
    lg = logmod.get_logger("unittest-comp")
    before = logmod.MESSAGES.get(
        'level="warning",component="unittest-comp"')
    lg.warning("thing %s failed", "alpha", attempt=1)
    for _ in range(4):  # same template inside the window: suppressed
        lg.warning("thing %s failed", "beta", attempt=2)
    err = capsys.readouterr().err
    lines = [json.loads(ln) for ln in err.splitlines()
             if ln.startswith("{")]
    ours = [r for r in lines if r.get("component") == "unittest-comp"]
    assert len(ours) == 1, "repeat suppression failed"
    rec = ours[0]
    assert rec["level"] == "warning" and rec["msg"] == "thing alpha failed"
    assert rec["attempt"] == 1 and "ts" in rec
    # every call counted, printed or not
    after = logmod.MESSAGES.get('level="warning",component="unittest-comp"')
    assert after - before == 5
    # ambient self-trace id lands on the line
    fake = types.SimpleNamespace(trace_id=b"\x42" * 16)
    token = TEL.set_active_trace(fake)
    try:
        lg.error("with trace")
    finally:
        TEL.reset_active_trace(token)
    traced = [json.loads(ln) for ln in capsys.readouterr().err.splitlines()
              if ln.startswith("{")]
    assert any(r.get("trace_id") == fake.trace_id.hex() for r in traced)


# ------------------------------------------------------- runtime gauges


def test_runtime_health_gauges():
    import gc

    from tempo_tpu.util import runtimestats

    runtimestats.install()
    gc.collect()
    lines = runtimestats.metrics_lines()
    text = "\n".join(lines)
    assert 'tempo_runtime_gc_collections_total{generation="2"}' in text
    assert "tempo_runtime_threads" in text
    assert "tempo_runtime_rss_bytes" in text
    # gauges carry live values
    assert runtimestats.THREADS.get() >= 1
    assert runtimestats.RSS.get() > 0


# ---------------------------------------------------- the interpreter probe


def _probe_delta(seconds: float, hz: float = 100.0) -> dict:
    """What the sampler's lateness probe adds while it runs `seconds`."""
    from tempo_tpu.util.runtimestats import interp_stats

    before = interp_stats()["probe"]
    PROF.start(hz=hz)
    time.sleep(seconds)
    PROF.stop()
    after = interp_stats()["probe"]
    return {k: after[k] - before[k] for k in after}


def _probe_idle():
    """Nobody else wants the interpreter: the sampler gets it back at
    once (what is left is the OS's wake-up, well under 2 ms)."""
    best = None
    for _ in range(3):  # a loaded test box: the quietest of three
        d = _probe_delta(0.4)
        assert d["ticks"] >= 10
        mean = d["late_seconds"] / d["ticks"]
        best = mean if best is None else min(best, mean)
        if best < 0.002:
            break
    assert best < 0.002, best


def _probe_contended():
    """Beside a thread spinning in Python the sampler waits one switch
    interval for every sample: the wait a thread that becomes runnable
    pays in a busy process."""
    import sys

    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(100))

    prev = sys.getswitchinterval()
    sys.setswitchinterval(0.05)
    t = threading.Thread(target=spin, daemon=True)
    t.start()
    try:
        d = _probe_delta(1.0, hz=50.0)
    finally:
        stop.set()
        sys.setswitchinterval(prev)
        t.join(10)
    assert d["ticks"] >= 5
    assert d["late_seconds"] / d["ticks"] >= 0.05 * 0.9, d
    assert d["late_over_20ms"] >= 0.8 * d["ticks"], d
    assert d["late_over_5ms"] >= d["late_over_20ms"]
    # the same numbers, where the sampler's status and /metrics show them
    assert PROF.status_snapshot()["sampler"]["probe"]["ticks"] >= d["ticks"]
    from tempo_tpu.util import runtimestats

    text = "\n".join(runtimestats.metrics_lines())
    assert 'tempo_runtime_gil_wait_seconds_bucket{le="0.02"}' in text
    assert "tempo_runtime_cpu_seconds_total " in text


def _probe_off():
    """TEMPO_PROFILE_HZ=0: no sampler, so the probe stays 0 (the readers
    then report nothing) while the CPU and wall clocks still run."""
    import os
    import subprocess
    import sys

    code = ("import json, time\n"
            "from tempo_tpu.util.profiler import PROF\n"
            "from tempo_tpu.util.kerneltel import TEL\n"
            "assert PROF.ensure_sampler() is False\n"
            "time.sleep(0.15)\n"
            "print(json.dumps(TEL.snapshot()['interp']))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=dict(os.environ, TEMPO_PROFILE_HZ="0"), timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    interp = json.loads(out.stdout.strip().splitlines()[-1])
    assert interp["probe"] == {"ticks": 0, "late_seconds": 0.0,
                               "late_over_5ms": 0, "late_over_20ms": 0}
    assert interp["cpu_seconds"] > 0 and interp["wall_seconds"] >= 0.15
    # in this process too: a stopped sampler adds nothing
    from tempo_tpu.util.runtimestats import interp_stats

    before = interp_stats()
    time.sleep(0.1)
    assert interp_stats()["probe"] == before["probe"]


def _probe_summed():
    """A tree's /status/kernels adds `interp` and the stages' CPU over
    its instances; rows without the CPU clock stay without it."""
    from tempo_tpu.services.proctree import tree_kernel_status

    def inst(k: int) -> dict:
        return {
            "device": {"count": 1},
            "stages": {"run:search_blocks": {"count": k, "seconds": 2.0 * k,
                                             "cpu_seconds": 0.5 * k},
                       "job:dispatch": {"count": k, "seconds": 0.1 * k}},
            "interp": {"cpu_seconds": 10.0 * k, "wall_seconds": 60.0,
                       "probe": {"ticks": 100 * k, "late_seconds": 0.25 * k,
                                 "late_over_5ms": 3 * k, "late_over_20ms": k}},
        }

    own = inst(1)
    own["stages"]["http:search"] = {"count": 4, "seconds": 1.0, "cpu_seconds": 0.25}
    total = tree_kernel_status(own, [({"index": i}, inst(i + 1)) for i in (1, 2)])
    assert total["interp"] == {
        "cpu_seconds": 60.0, "wall_seconds": 180.0,
        "probe": {"ticks": 600, "late_seconds": 1.5, "late_over_5ms": 18,
                  "late_over_20ms": 6}}
    assert total["stages"]["run:search_blocks"] == {
        "count": 6, "seconds": 12.0, "cpu_seconds": 3.0}
    assert total["stages"]["job:dispatch"] == {"count": 6, "seconds": pytest.approx(0.6)}
    assert total["stages"]["http:search"]["cpu_seconds"] == 0.25


def _probe_scrapes():
    """Scrapes that come together add the CPU's delta once: the counter
    never passes the process's clock and never goes backwards."""
    import threading

    from tempo_tpu.util import runtimestats

    seen, stop = [], threading.Event()

    def scrape():
        while not stop.is_set():
            runtimestats.refresh()
            seen.append((runtimestats.CPU.get(), time.process_time()))

    threads = [threading.Thread(target=scrape) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    stop.set()
    for t in threads:
        t.join()
    assert len(seen) > 8
    assert all(cpu <= clock + 1e-9 for cpu, clock in seen)
    runtimestats.refresh()
    assert runtimestats.CPU.get() >= max(cpu for cpu, _ in seen)


_PROBE_CASES = {"idle": _probe_idle, "contended": _probe_contended,
                "off": _probe_off, "summed": _probe_summed,
                "scrapes": _probe_scrapes}


@pytest.mark.parametrize("case", sorted(_PROBE_CASES))
def test_interp_probe(case):
    _PROBE_CASES[case]()


# ------------------------------------------------------ strict exposition


def test_new_families_strict_openmetrics(monkeypatch):
    from test_observability import parse_openmetrics_strict

    from tempo_tpu.util.metrics import render_openmetrics

    monkeypatch.setenv(profmod.LOCK_PROFILE_ENV, "1")
    # populate every new family
    PROF.start(hz=100.0)
    time.sleep(0.1)
    PROF.stop()
    lk = timed_lock("expo_lock")
    with lk:
        pass
    logmod.get_logger("expo").warning("expo message")
    text = render_openmetrics(TEL.metrics_lines(),
                              helps=TEL.help_entries()) + "# EOF\n"
    fams = parse_openmetrics_strict(text)
    assert fams.get("tempo_profile_samples") == "counter"
    assert fams.get("tempo_lock_acquisitions") == "counter"
    assert fams.get("tempo_log_messages") == "counter"
    assert fams.get("tempo_runtime_gc_collections") == "counter"
    assert fams.get("tempo_runtime_threads") == "gauge"
    assert fams.get("tempo_runtime_rss_bytes") == "gauge"
    assert fams.get("tempo_runtime_cpu_seconds") == "counter"
    assert fams.get("tempo_runtime_gil_wait_seconds") == "histogram"
    # a contended wait makes the histogram family appear too
    lk2 = timed_lock("expo_lock2")
    lk2.acquire()
    t = threading.Thread(target=lambda: (lk2.acquire(), lk2.release()))
    t.start()
    time.sleep(0.05)
    lk2.release()
    t.join()
    text = render_openmetrics(TEL.metrics_lines(),
                              helps=TEL.help_entries()) + "# EOF\n"
    fams = parse_openmetrics_strict(text)
    assert fams.get("tempo_lock_wait_seconds") == "histogram"


# ------------------------------------------------------------------- e2e


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_slow_query_e2e_chaos_to_artifact(tmp_path, monkeypatch, capsys):
    """The acceptance loop: a chaos `slow-launch` rule makes a search
    slow; the slow-query log entry carries BOTH a self-trace id and a
    profile artifact id; the artifact downloads over HTTP and
    `tempo-tpu-cli profile artifact` renders it."""
    from tempo_tpu.chaos import plane
    from tempo_tpu.services.app import App, AppConfig
    from tempo_tpu.services.ingester import IngesterConfig
    from tempo_tpu.util.testdata import make_traces
    from tempo_tpu.wire import otlp_json

    monkeypatch.setenv("TEMPO_SLO_SEARCH_P99_S", "0.05")
    monkeypatch.setenv(profmod.PROFILE_HZ_ENV, "97")
    # the drill repeats one slow query until the profiler catches it
    # in-flight; a result-cache hit would answer in microseconds and
    # never cross the slow threshold again
    monkeypatch.setenv("TEMPO_RESULT_CACHE", "0")
    cfg = AppConfig(
        storage_path=str(tmp_path / "store"),
        http_port=_free_port(),
        compaction_cycle_s=9999,
        self_tracing_tenant="self",
        ingester=IngesterConfig(max_trace_idle_s=0.0, max_block_age_s=0.0,
                                flush_check_period_s=9999),
    )
    app = App(cfg)
    app.start()
    app.serve_http(background=True)
    base = f"http://127.0.0.1:{cfg.http_port}"
    try:
        assert PROF.sampling, "app start did not arm the sampler"
        for _, tr in make_traces(8, seed=21, n_spans=4):
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/traces", data=otlp_json.dumps(tr).encode(),
                headers={"Content-Type": "application/json"}), timeout=10)
        app.ingester.flush_all()
        app.db.poll_now()
        # warm the read path, then zero out the device round-trip cost
        # estimate so the router must pick the DEVICE engine (tiny test
        # blocks with cached host arrays otherwise always scan host and
        # a slow-LAUNCH rule would have nothing to slow), and pay the
        # device compile storm outside the chaos window
        from tempo_tpu.db import route as route_mod

        q = urllib.parse.quote('{ duration > 1ms }')
        for _ in range(3):
            urllib.request.urlopen(f"{base}/api/search?q={q}&limit=10",
                                   timeout=60)
        monkeypatch.setattr(route_mod, "link_rtt_ms", lambda: -1.0)
        urllib.request.urlopen(f"{base}/api/search?q={q}&limit=10",
                               timeout=120)
        time.sleep(0.3)  # clear the capture stampede guard
        # chaos slow-launch: every device launch pays 120ms -> the
        # query crosses its SLO class p99 threshold deterministically
        plane.configure([{"site": "device.launch", "action": "latency",
                          "latency_s": 0.12}])
        urllib.request.urlopen(f"{base}/api/search?q={q}&limit=10",
                               timeout=60)
        plane.reset_for_tests()
        with urllib.request.urlopen(base + "/status/kernels",
                                    timeout=10) as r:
            status = json.loads(r.read())
        slow = [sq for sq in status["slow_queries"]
                if sq["op"] == "search" and sq["profile_artifact_id"]]
        assert slow, f"no captured slow query in {status['slow_queries']}"
        entry = slow[0]
        assert entry["self_trace_id"], "entry lost its self-trace id"
        aid = entry["profile_artifact_id"]
        # /status/profile shows the sampler + the artifact
        with urllib.request.urlopen(base + "/status/profile",
                                    timeout=10) as r:
            prof = json.loads(r.read())
        assert prof["sampler"]["running"]
        assert any(a["id"] == aid for a in prof["artifacts"])
        # the artifact downloads and is folded text
        with urllib.request.urlopen(
                f"{base}/debug/profile/artifact/{aid}", timeout=10) as r:
            art = r.read().decode()
        assert "slow-query profile" in art
        assert f"self_trace_id={entry['self_trace_id']}" in art
        # burst profile endpoints still serve both formats
        with urllib.request.urlopen(
                f"{base}/debug/profile?seconds=0.2&format=folded",
                timeout=30) as r:
            assert r.status == 200
        # the CLI renders the artifact (the dogfood loop's last hop)
        from tempo_tpu.cli.__main__ import main as cli_main

        capsys.readouterr()
        cli_main(["profile", "artifact", aid, "--target", base])
        out = capsys.readouterr().out
        assert "samples" in out and "slow-query profile" in out
        # and the lock table endpoint answers (no locks armed -> empty)
        cli_main(["profile", "lock", "--target", base])
        assert "lock" in capsys.readouterr().out.lower()
    finally:
        app.stop()
