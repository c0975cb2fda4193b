"""Multi-process topology e2e: 2 ingesters + distributor + querier as
separate OS processes over a shared ring-KV directory and storage path.

The analog of the reference's TestMicroservicesWithKVStores
(integration/e2e/e2e_test.go:130) -- real process boundaries, HTTP
data plane, file-KV control plane. A CPU harness: every role runs with
JAX_PLATFORMS=cpu, since several kernel-launching roles share the host
and a chip belongs to one process.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from tempo_tpu.util.testdata import make_traces
from tempo_tpu.wire import otlp_json

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _spawn(target, port, storage, kv, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.Popen(
        [sys.executable, "-m", "tempo_tpu.services.app",
         f"--target={target}", "--http.port", str(port),
         "--storage.path", storage, "--kv.dir", kv, *extra],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )


def _wait_ready(port, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/ready", timeout=1) as r:
                if r.status == 200:
                    return
        except (urllib.error.URLError, ConnectionError, OSError):
            pass
        time.sleep(0.3)
    raise TimeoutError(f"port {port} never became ready")


import contextlib


@contextlib.contextmanager
def _two_ingester_topology(tmp_path, rf=2):
    """2 ingesters + distributor(rf) + querier as real processes over a
    shared storage path + file ring-KV; yields (ports, procs-by-name)."""
    storage = str(tmp_path / "storage")
    kv = str(tmp_path / "kv")
    ports = {r: _free_port() for r in ("ing1", "ing2", "dist", "query")}
    procs = {}
    try:
        for name in ("ing1", "ing2"):
            procs[name] = _spawn("ingester", ports[name], storage, kv,
                                 ("--instance.id", name))
        _wait_ready(ports["ing1"])
        _wait_ready(ports["ing2"])
        procs["dist"] = _spawn("distributor", ports["dist"], storage, kv,
                               ("--replication.factor", str(rf)))
        procs["query"] = _spawn("querier", ports["query"], storage, kv)
        _wait_ready(ports["dist"])
        _wait_ready(ports["query"])
        yield ports, procs
    finally:
        for p in procs.values():
            p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow
def test_microservices_topology(tmp_path):
    with _two_ingester_topology(tmp_path, rf=2) as (ports, procs):

        traces = make_traces(10, seed=55, n_spans=4)
        for _, tr in traces:
            req = urllib.request.Request(
                f"http://127.0.0.1:{ports['dist']}/v1/traces",
                data=otlp_json.dumps(tr).encode(),
                headers={"Content-Type": "application/json"},
            )
            assert urllib.request.urlopen(req, timeout=10).status == 200

        # live read through the querier -> remote ingester find
        tid, tr = traces[0]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ports['query']}/api/traces/{tid.hex()}", timeout=15
        ) as r:
            got = otlp_json.loads(r.read())
        assert got.span_count() == tr.span_count()

        # live search through the querier -> remote ingester search
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ports['query']}/api/search?limit=100", timeout=15
        ) as r:
            hits = {t["traceID"] for t in json.loads(r.read())["traces"]}
        assert {tid.hex() for tid, _ in traces} <= hits

        # flush both ingesters -> blocks in shared storage -> backend read
        for name in ("ing1", "ing2"):
            urllib.request.urlopen(
                urllib.request.Request(f"http://127.0.0.1:{ports[name]}/flush", data=b""),
                timeout=15,
            )
        deadline = time.time() + 20
        got = None
        tid, tr = traces[1]
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{ports['query']}/api/traces/{tid.hex()}", timeout=15
                ) as r:
                    got = otlp_json.loads(r.read())
                break
            except urllib.error.HTTPError:
                time.sleep(1)
        assert got is not None and got.span_count() == tr.span_count()


@pytest.mark.slow
def test_frontend_remote_querier_pull(tmp_path):
    """1 dispatcher-only query-frontend + 2 standalone queriers pulling
    jobs over /internal/jobs: both queriers demonstrably execute search
    jobs (the reference's querier-worker attach model,
    modules/querier/worker/frontend_processor.go:57-80 +
    modules/frontend/v1/frontend.go:50-90)."""
    storage = str(tmp_path / "storage")
    kv = str(tmp_path / "kv")
    ports = {r: _free_port() for r in ("ing", "fe", "q1", "q2")}
    procs = []
    try:
        procs.append(_spawn("ingester", ports["ing"], storage, kv,
                            ("--instance.id", "ing-a")))
        _wait_ready(ports["ing"])

        # push + flush so the backend holds blocks to search
        from tempo_tpu.transport.client import HTTPIngesterClient
        from tempo_tpu.wire.segment import segment_for_write

        traces = make_traces(30, seed=21, n_spans=4)
        client = HTTPIngesterClient(f"http://127.0.0.1:{ports['ing']}")
        for i in range(0, 30, 10):  # three flushes -> three blocks
            batch = []
            for tid, tr in traces[i : i + 10]:
                lo, hi = tr.time_range_nanos()
                batch.append((tid, lo // 10**9, hi // 10**9 + 1,
                              segment_for_write(tr, lo // 10**9, hi // 10**9 + 1)))
            client.push_segments("single-tenant", batch)
            urllib.request.urlopen(
                urllib.request.Request(f"http://127.0.0.1:{ports['ing']}/flush", data=b""),
                timeout=20,
            )

        fe_addr = f"http://127.0.0.1:{ports['fe']}"
        procs.append(_spawn("query-frontend", ports["fe"], storage, kv))
        for q in ("q1", "q2"):
            procs.append(_spawn("querier", ports[q], storage, kv,
                                ("--querier.frontend-address", fe_addr)))
        _wait_ready(ports["fe"])
        _wait_ready(ports["q1"])
        _wait_ready(ports["q2"])

        # several searches + finds through the frontend: every job must
        # be executed by a REMOTE querier (the frontend has no workers)
        deadline = time.time() + 60
        hits = set()
        while time.time() < deadline and len(hits) < 30:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{ports['fe']}/api/search?limit=100", timeout=30
            ) as r:
                hits = {t["traceID"] for t in json.loads(r.read())["traces"]}
            time.sleep(0.5)
        assert {tid.hex() for tid, _ in traces} <= hits

        tid, tr = traces[7]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ports['fe']}/api/traces/{tid.hex()}", timeout=30
        ) as r:
            got = otlp_json.loads(r.read())
        assert got.span_count() == tr.span_count()

        # enough jobs that BOTH queriers must have pulled some
        for i in range(10):
            urllib.request.urlopen(
                f"http://127.0.0.1:{ports['fe']}/api/search?limit=100", timeout=30)
            urllib.request.urlopen(
                f"http://127.0.0.1:{ports['fe']}/api/traces/{traces[i][0].hex()}",
                timeout=30)

        def metric(port, name):
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                for line in r.read().decode().splitlines():
                    if line.startswith(name + " "):
                        return int(line.split()[1])
            return 0

        ex1 = metric(ports["q1"], "tempo_querier_worker_jobs_executed_total")
        ex2 = metric(ports["q2"], "tempo_querier_worker_jobs_executed_total")
        assert ex1 > 0 and ex2 > 0, (ex1, ex2)
        assert metric(ports["fe"], "tempo_frontend_jobs_remote_total") > 0
        assert metric(ports["fe"], "tempo_frontend_jobs_local_total") == 0
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow
def test_ingester_crash_restart_replays(tmp_path):
    """Kill an ingester before flush; its restart replays the WAL and the
    data stays queryable (the reference's ScalableSingleBinary restart
    scenario + WAL replay, e2e_test.go:314, ingester.go:326-400)."""
    storage = str(tmp_path / "storage")
    kv = str(tmp_path / "kv")
    p_ing = _free_port()
    p_q = _free_port()
    procs = []
    try:
        ing = _spawn("ingester", p_ing, storage, kv, ("--instance.id", "ing-x"))
        procs.append(ing)
        _wait_ready(p_ing)
        # push straight to the ingester via the internal API (distributor
        # path is covered by the other test; here the crash is the point)
        from tempo_tpu.transport.client import HTTPIngesterClient
        from tempo_tpu.wire.segment import segment_for_write

        traces = make_traces(8, seed=77, n_spans=3)
        client = HTTPIngesterClient(f"http://127.0.0.1:{p_ing}")
        batch = []
        for tid, tr in traces:
            lo, hi = tr.time_range_nanos()
            batch.append((tid, lo // 10**9, hi // 10**9 + 1,
                          segment_for_write(tr, lo // 10**9, hi // 10**9 + 1)))
        client.push_segments("single-tenant", batch)

        # crash hard (no flush), then restart with the same instance id
        ing.kill()
        ing.wait()
        ing2 = _spawn("ingester", p_ing, storage, kv, ("--instance.id", "ing-x"))
        procs.append(ing2)
        _wait_ready(p_ing)

        # replay turned the WAL into a backend block: a querier sees it
        q = _spawn("querier", p_q, storage, kv)
        procs.append(q)
        _wait_ready(p_q)
        deadline = time.time() + 30
        got = None
        tid, tr = traces[0]
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{p_q}/api/traces/{tid.hex()}", timeout=10
                ) as r:
                    got = otlp_json.loads(r.read())
                break
            except urllib.error.HTTPError:
                time.sleep(1)
        assert got is not None and got.span_count() == tr.span_count()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow
def test_gossip_topology(tmp_path):
    """Processes form the ring over GOSSIP (no shared KV dir): an
    ingester seeds, distributor + querier join by seed address — the
    memberlist topology (modules.go:288-316)."""
    storage = str(tmp_path / "storage")
    ports = {r: _free_port() for r in ("ing", "dist", "query")}
    gports = {r: _free_port() for r in ("ing", "dist", "query")}
    seed = f"127.0.0.1:{gports['ing']}"

    def spawn(target, name, extra=()):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        return subprocess.Popen(
            [sys.executable, "-m", "tempo_tpu.services.app",
             f"--target={target}", "--http.port", str(ports[name]),
             "--storage.path", storage,
             "--memberlist.bind", f"127.0.0.1:{gports[name]}", *extra],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )

    procs = []
    try:
        procs.append(spawn("ingester", "ing", ("--instance.id", "g-ing",)))
        _wait_ready(ports["ing"])
        procs.append(spawn("distributor", "dist", ("--memberlist.join", seed)))
        procs.append(spawn("querier", "query", ("--memberlist.join", seed)))
        _wait_ready(ports["dist"])
        _wait_ready(ports["query"])

        traces = make_traces(6, seed=33, n_spans=3)
        deadline = time.time() + 30
        pushed = False
        while time.time() < deadline and not pushed:
            try:  # distributor needs a gossip round to see the ingester
                for _, tr in traces:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{ports['dist']}/v1/traces",
                        data=otlp_json.dumps(tr).encode(),
                        headers={"Content-Type": "application/json"})
                    urllib.request.urlopen(req, timeout=10)
                pushed = True
            except urllib.error.HTTPError:
                time.sleep(1)
        assert pushed

        tid, tr = traces[0]
        got = None
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{ports['query']}/api/traces/{tid.hex()}",
                    timeout=15,
                ) as r:
                    got = otlp_json.loads(r.read())
                break
            except urllib.error.HTTPError:
                time.sleep(1)
        assert got is not None and got.span_count() == tr.span_count()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow
def test_remote_generator_blob_plane(tmp_path):
    """Standalone metrics-generator process: the distributor's tap ships
    otlp-proto BLOBS sliced from segments over /internal/genpush (zero
    decode on the distributor), shuffle-sharded via the generator ring;
    the generator aggregates them into span-metrics series."""
    storage = str(tmp_path / "storage")
    kv = str(tmp_path / "kv")
    os.makedirs(storage, exist_ok=True)
    ports = {t: _free_port() for t in ("ingester", "distributor", "generator")}
    procs = [
        _spawn("ingester", ports["ingester"], storage, kv),
        _spawn("metrics-generator", ports["generator"], storage, kv),
        _spawn("distributor", ports["distributor"], storage, kv),
    ]
    try:
        for p in ports.values():
            _wait_ready(p)
        from tempo_tpu.wire import otlp_pb

        traces = make_traces(8, seed=61, n_spans=3)
        base = f"http://127.0.0.1:{ports['distributor']}"
        for _, t in traces:
            req = urllib.request.Request(
                base + "/v1/traces", data=otlp_pb.encode_trace(t),
                headers={"Content-Type": "application/x-protobuf"})
            with urllib.request.urlopen(req, timeout=15) as r:
                assert r.status == 200
        # the tap is async + remote: poll the GENERATOR's metrics
        deadline = time.time() + 20
        total = 0
        while time.time() < deadline:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{ports['generator']}/metrics",
                    timeout=10) as r:
                lines = r.read().decode().splitlines()
            total = sum(int(l.rsplit(" ", 1)[1]) for l in lines
                        if l.startswith("traces_spanmetrics_calls_total"))
            if total >= sum(t.span_count() for _, t in traces):
                break
            time.sleep(0.3)
        assert total == sum(t.span_count() for _, t in traces), total
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow
def test_rf2_survives_ingester_kill(tmp_path):
    """RF=2 eventual consistency (pkg/ring EventuallyConsistentStrategy,
    minSuccess=1): with one of two ingesters SIGKILLed -- and still
    listed healthy in the ring (no heartbeat timeout yet) -- writes
    keep succeeding on the surviving replica and every trace stays
    readable through the querier."""
    import signal

    with _two_ingester_topology(tmp_path, rf=2) as (ports, procs):

        def push(tr):
            req = urllib.request.Request(
                f"http://127.0.0.1:{ports['dist']}/v1/traces",
                data=otlp_json.dumps(tr).encode(),
                headers={"Content-Type": "application/json"})
            assert urllib.request.urlopen(req, timeout=15).status == 200

        before = make_traces(5, seed=71, n_spans=3)
        for _, tr in before:
            push(tr)

        # hard-kill one replica; its ring entry stays "healthy" until the
        # heartbeat staleness window, so the distributor still tries it
        procs["ing2"].send_signal(signal.SIGKILL)
        procs["ing2"].wait(timeout=10)

        after = make_traces(5, seed=72, n_spans=3)
        for _, tr in after:
            push(tr)  # minSuccess=1: the surviving replica is enough

        for tid, tr in before + after:
            got = None
            deadline = time.time() + 20
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{ports['query']}/api/traces/{tid.hex()}",
                            timeout=15) as r:
                        got = otlp_json.loads(r.read())
                    break
                except urllib.error.HTTPError:
                    time.sleep(0.5)
            assert got is not None and got.span_count() == tr.span_count(), tid.hex()
