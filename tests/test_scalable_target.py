"""--target scalable-single-binary as a process tree (1 + 3 CPU processes):
its answers for every read-mix shape equal the numpy oracle's and the single
binary's, also with a querier killed mid-run; its status speaks for the tree;
it drains on SIGTERM and leaves nothing behind when its parent is killed.

A CPU harness: every process runs with JAX_PLATFORMS=cpu (nothing is pinned
there). Every test has its own time limit and every server its own process
group, killed by a fixture finaliser; ports are ephemeral and every directory
is a tmp_path.
"""

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import corpus as corpus_lib  # noqa: E402
from benchmarks.lib import harness as H  # noqa: E402
from benchmarks.lib.server import Client  # noqa: E402
from tempo_tpu.services import proctree  # noqa: E402

LIMIT_S = 60
SHAPES = ("attr_eq", "duration_gt", "struct_desc", "rate_service",
          "tag_service", "find_hit", "find_miss")
N_BLOCKS, TRACES, SPANS_PER = 3, 400, 8


class _Limit:
    """`with _Limit():` -- SIGALRM after LIMIT_S (pytest runs tests on the
    worker's main thread), so no test waits without an end."""

    def __enter__(self):
        def fire(*_):
            raise TimeoutError(f"no end after {LIMIT_S} s")

        self.prev = signal.signal(signal.SIGALRM, fire)
        signal.alarm(LIMIT_S)

    def __exit__(self, *exc):
        signal.alarm(0)
        signal.signal(signal.SIGALRM, self.prev)
        return False


@pytest.fixture(autouse=True)
def _time_limit():
    with _Limit():
        yield


class Proc:
    """One server in a process group of its own."""

    def __init__(self, storage, log_path, args=()):
        self.port = proctree.free_port()
        self.log_path = log_path
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        env.pop("XLA_FLAGS", None)  # one CPU device a process
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tempo_tpu.services.app",
             "--storage.path", storage, "--http.port", str(self.port), *args],
            cwd=REPO, env=env, stdout=open(log_path, "ab"),
            stderr=subprocess.STDOUT, start_new_session=True)

    def get(self, path, headers=None):
        req = urllib.request.Request(f"http://127.0.0.1:{self.port}{path}",
                                     headers=headers or {})
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    def kill_group(self):
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass

    def log(self):
        with open(self.log_path, errors="replace") as f:
            return f.read()[-3000:]


def tree_args(n=4, extra=()):
    return ("--target", "scalable-single-binary", "--scalable.instances",
            str(n), *extra)


def alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def wait_gone(pids, seconds: float) -> list:
    deadline = time.time() + seconds
    while time.time() < deadline and any(alive(p) for p in pids):
        time.sleep(0.1)
    return [p for p in pids if alive(p)]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """A tiny seeded corpus by the benchmark's own builder (the program's
    block writer + the oracle's columns), dated now: block b fills the
    hour that began (b + 1) x (1 h + 180 s) before the top of this hour."""
    with _Limit():
        out = str(tmp_path_factory.mktemp("corpus"))
        now_ns = time.time_ns()
        top_ns = now_ns - now_ns % corpus_lib.HOUR_NS
        blocks = []
        for b in range(N_BLOCKS):
            base = top_ns - (b + 1) * (corpus_lib.HOUR_NS + 180 * 10**9)
            corpus_lib.build_block(argparse.Namespace(
                out=out, block=b, seed=26, traces=TRACES, spans_per=SPANS_PER,
                n_res=1024, attrs_per_span=2, base_time_ns=base))
            with open(os.path.join(out, f"block{b}.json")) as f:
                blocks.append(json.load(f))
        manifest = {"path": out, "blocks": blocks, "tenant": corpus_lib.TENANT}
        config = {"corpus": {"block_popularity": [0.5, 0.3, 0.2],
                             "attrs_per_span": 2}}
        mix = H.load_json(os.path.join(REPO, "benchmarks", "mixes",
                                       "read-mix.json"))
        return manifest, H.Env(config, mix, manifest, seed=26)


@pytest.fixture(scope="module")
def pair(corpus, tmp_path_factory):
    """The tree and the single binary over copies of the same blocks."""
    manifest, _ = corpus
    d = tmp_path_factory.mktemp("pair")
    servers = {}
    with _Limit():
        try:
            for name, args in (("tree", tree_args(4, ("--self-tracing.tenant", "self"))),
                               ("all", ("--target=all",))):
                storage = str(d / name)
                shutil.copytree(os.path.join(manifest["path"], "store"), storage)
                servers[name] = Proc(storage, str(d / f"{name}.log"), args)
            for name, s in servers.items():
                try:
                    proctree.wait_ready(s.port, timeout=LIMIT_S - 5, proc=s.proc)
                except Exception as e:
                    raise AssertionError(f"{name}: {e}\n{s.log()}")
        except BaseException:
            for s in servers.values():
                s.kill_group()
            raise
    yield servers
    for s in servers.values():
        s.kill_group()


def ops_of(shape: str, env, n: int, seed: int) -> list:
    rnd = random.Random(f"{seed}-{shape}")
    mod = H.load_plugin("shapes", shape)
    params = {"duration_gt": {"ms": [900, 990]},
              "struct_desc": {"ms": [300, 700]}}.get(shape, {})
    return [{"shape": shape, "i": i, **mod.build(rnd, env, params)}
            for i in range(n)]


def ask(server: Proc, op: dict, env) -> dict:
    cl = Client(server.port, timeout=30)
    try:
        return H.send(op, env, cl, "test")
    finally:
        cl.close()


def answer_of(res: dict):
    """What has to be equal between two deployments: the set of trace ids of
    a search, the series of a rate(), the spans of a found trace."""
    doc = json.loads(res["data"]) if res["status"] == 200 else None
    if doc is None:
        return res["status"]
    if "traces" in doc:
        return sorted(t["traceID"] for t in doc["traces"])
    if "data" in doc:
        return doc["data"]["result"]
    from benchmarks.lib.oracle import spans_of_otlp_json

    return sorted(spans_of_otlp_json(doc))


@pytest.mark.parametrize("shape", SHAPES)
def test_tree_equals_oracle_and_single_binary(pair, corpus, shape):
    _, env = corpus
    for op in ops_of(shape, env, 3, seed=1):
        got = {name: ask(s, op, env) for name, s in pair.items()}
        assert answer_of(got["tree"]) == answer_of(got["all"]), (shape, op)
        results = list(got.values())
        H.check_all(results, env)
        assert all(r["ok"] for r in results), [(r["status"], r["detail"])
                                               for r in results]


def _launches(snap: dict) -> int:
    return sum(k["compiles"] + k["cache_hits"] for k in snap["kernels"])


SUMMED = (("jit_cache", "compiles_total"), ("staging", "cache_hits"),
          ("staging", "transfer_bytes_total"),
          ("staged_cache", "budget_bytes"), ("staged_cache", "entries"))


def _counters(snaps: list) -> list:
    """What has to stand still for a read to count: the launches, the
    staging counters and the rows a job in flight adds to (its wire
    handlers' and its run's)."""
    return [[_launches(o)] + [o[sec][key] for sec, key in SUMMED]
            + sorted((n, r["count"]) for n, r in o["stages"].items()
                     if n.startswith(("run:", "job:")))
            for o in snaps]


def _settled_status(tree: Proc):
    """(the tree's /status/kernels, each instance's own) read while nothing
    moved: a search's answer can come back before its hedged or abandoned
    twin has run, so the instances are read before AND after the total and
    the three reads count only when the two outer ones agree."""
    def instances(rows):
        own = [tree.get("/status/kernels?scope=instance")]
        for r in rows[1:]:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{r['port']}/status/kernels", timeout=30) as f:
                own.append(json.loads(f.read()))
        return own

    rows = tree.get("/status/kernels")["instances"]
    deadline = time.time() + 30
    while True:
        before = instances(rows)
        total = tree.get("/status/kernels")
        after = instances(rows)
        if _counters(before) == _counters(after):
            return total, after
        assert time.time() < deadline, "the tree's counters never stood still"
        time.sleep(0.3)


def test_status_is_the_sum_of_the_instances(pair, corpus):
    _, env = corpus
    tree = pair["tree"]
    for op in ops_of("attr_eq", env, 4, seed=2) + ops_of("rate_service", env, 2, seed=2):
        assert ask(tree, op, env)["status"] == 200
    total, own = _settled_status(tree)
    rows = total["instances"]
    assert [r["index"] for r in rows] == [0, 1, 2, 3]
    assert all(alive(r["pid"]) for r in rows[1:])
    assert "instances" not in own[0]
    assert total["device"]["count"] == sum(o["device"]["count"] for o in own) == 4
    assert total["device"]["platform"] == "cpu"
    for sec, key in SUMMED:
        assert total[sec][key] == sum(o[sec][key] for o in own), (sec, key)
    assert _launches(total) == sum(_launches(o) for o in own) > 0
    jobs = total["dispatch"]["jobs"]
    assert jobs["remote"] > 0 and jobs["local"] + jobs["remote"] == sum(
        w["jobs"] for w in total["dispatch"]["by_worker"].values())
    assert total["stages"]["job:dispatch"]["count"] >= jobs["remote"]
    # the process that ran a job recorded its run:<kind> row, once
    runs = {n: r for n, r in total["stages"].items() if n.startswith("run:")}
    assert sum(r["count"] for r in runs.values()) >= jobs["local"]
    # a wire job (same-key jobs of one pull travel as ONE `multi` job) is
    # one run:* row on the querier that pulled it; it was encoded on its
    # way out and its result on the way back, and each was decoded on the
    # other side (a job past its deadline is answered without a run)
    wire_jobs = sum(r["count"] for o in own[1:]
                    for n, r in o["stages"].items() if n.startswith("run:"))
    wire = total["stages"]
    assert wire["job:encode"]["count"] >= 2 * wire_jobs > 0
    assert wire["job:encode"]["count"] == wire["job:decode"]["count"]
    for name, row in runs.items():
        assert row["count"] == sum(
            o["stages"].get(name, {}).get("count", 0) for o in own), name
        assert row["cpu_seconds"] == pytest.approx(sum(
            o["stages"].get(name, {}).get("cpu_seconds", 0.0) for o in own), abs=1e-4)
    assert any(o["stages"].get("run:search_blocks", {}).get("count", 0)
               for o in own[1:]), "no querier recorded a run:search_blocks row"
    assert set(total["interp"]) == {"cpu_seconds", "wall_seconds", "probe"}
    assert total["interp"]["probe"]["ticks"] >= max(
        o["interp"]["probe"]["ticks"] for o in own)
    placed = total["affinity"]["jobs"]
    assert placed.get("own", 0) + placed.get("steal", 0) > 0
    hbm = tree.get("/status/cost")["hbm"]["per_device_memory_stats"]
    assert sorted(d["id"] for d in hbm) == [0, 1, 2, 3]


def test_remote_spans_hang_under_the_frontends_root(pair, corpus):
    _, env = corpus
    tree = pair["tree"]
    t0 = time.time()
    for op in ops_of("attr_eq", env, 6, seed=3):
        assert ask(tree, op, env)["status"] == 200
    hdr = {"X-Scope-OrgID": "self"}
    deadline = time.time() + 30
    while True:
        found = tree.get("/api/search?q=%7B%20true%20%7D&limit=200"
                         f"&start={int(t0) - 1}&end={int(time.time()) + 60}", hdr)
        roots = [t for t in found["traces"]
                 if t.get("rootTraceName") == "frontend.search"]
        trees = []
        for t in roots:
            doc = tree.get("/api/traces/" + t["traceID"], hdr)
            spans = [sp for rs in doc["resourceSpans"]
                     for ss in rs["scopeSpans"] for sp in ss["spans"]]
            trees.append(spans)
        remote = [spans for spans in trees
                  if any(_attr(sp, "querier") for sp in spans)]
        if remote or time.time() > deadline:
            break
        time.sleep(0.5)  # the shipper is asynchronous
    assert remote, f"no search of {len(trees)} has a span a querier recorded"
    spans = remote[0]
    by_id = {sp["spanId"]: sp for sp in spans}
    root = next(sp for sp in spans if not sp.get("parentSpanId"))
    assert root["name"] == "frontend.search"
    far = next(sp for sp in spans if _attr(sp, "querier"))
    chain, sp = [], far
    while sp.get("parentSpanId"):
        sp = by_id[sp["parentSpanId"]]  # KeyError = an orphan: not one tree
        chain.append(sp["name"])
    assert chain[-1] == "frontend.search" and any(n.startswith("job:") for n in chain)
    names = {sp["name"] for sp in spans}
    assert {"queue-wait", "job:dispatch", "job:result"} <= names, names
    disp = next(sp for sp in spans if sp["name"] == "job:dispatch"
                and _attr(sp, "remote"))
    assert _attr(disp, "worker").startswith("querier-")
    assert _attr(disp, "placement") in ("owner", "stolen", "unowned")


def _attr(span: dict, key: str):
    for a in span.get("attributes", []):
        if a["key"] == key:
            v = a["value"]
            return next(iter(v.values())) if isinstance(v, dict) else v
    return None


def test_querier_killed_mid_run_answers_stay_complete(pair, corpus):
    """SIGKILL one querier while searches run: every answer is complete
    (the oracle's exact set), none is a 5xx, and the child comes back."""
    _, env = corpus
    tree = pair["tree"]
    before = {r["index"]: r["pid"] for r in tree.get("/status/kernels")["instances"][1:]}
    ops = (ops_of("attr_eq", env, 8, seed=4) + ops_of("rate_service", env, 4, seed=4)
           + ops_of("find_hit", env, 4, seed=4) + ops_of("tag_service", env, 4, seed=4))
    random.Random(4).shuffle(ops)
    results = []
    for i, op in enumerate(ops):
        if i == 3:
            os.kill(before[2], signal.SIGKILL)
        results.append(ask(tree, op, env))
    assert [r["status"] for r in results] == [200] * len(ops)
    H.check_all(results, env)
    assert all(r["ok"] for r in results), [r["detail"] for r in results if not r["ok"]]
    proctree.wait_ready(tree.port, timeout=40, proc=tree.proc)  # respawned + attached
    after = {r["index"]: r["pid"] for r in tree.get("/status/kernels")["instances"][1:]}
    assert after[2] != before[2] and alive(after[2])
    assert {i: p for i, p in after.items() if i != 2} == \
        {i: p for i, p in before.items() if i != 2}


@pytest.fixture
def bare_tree(tmp_path):
    """A tree over an empty store (start-up and shutdown need no block)."""
    s = Proc(str(tmp_path / "store"), str(tmp_path / "tree.log"), tree_args(4))
    try:
        proctree.wait_ready(s.port, timeout=LIMIT_S - 15, proc=s.proc)
        pids = [r["pid"] for r in s.get("/status/kernels")["instances"][1:]]
        assert len(pids) == 3 and all(alive(p) for p in pids), s.log()
        yield s, pids
    finally:
        s.kill_group()


def test_ready_needs_every_querier_and_sigterm_drains(bare_tree):
    s, pids = bare_tree
    s.proc.send_signal(signal.SIGTERM)
    assert s.proc.wait(timeout=30) == 0, s.log()
    assert wait_gone(pids, 5) == []
    with open(s.log_path, errors="replace") as f:  # the override is said aloud
        assert "fewer in-process workers than frontend_workers" in f.read()


def test_sigkill_of_the_parent_takes_the_children(bare_tree):
    s, pids = bare_tree
    s.proc.kill()  # the parent alone, not its group
    s.proc.wait(timeout=10)
    assert wait_gone(pids, 5) == [], "a querier outlived its parent"


@pytest.mark.parametrize("n", [1, 2, 4])
def test_pin_env_is_one_chip_a_process(n):
    envs = [proctree.pin_env(i, n) for i in range(n)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == [str(i) for i in range(n)]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == n
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_ADDRESSES"].endswith(":" + e["TPU_PROCESS_PORT"])
        assert all(isinstance(v, str) for v in e.values())
    assert proctree.pin_env(0, n) == envs[0]  # pure
    with pytest.raises(ValueError):
        proctree.pin_env(n, n)


def test_more_instances_than_chips_is_a_start_up_error(monkeypatch):
    from tempo_tpu.services import app as appmod

    monkeypatch.setattr(proctree, "on_cpu", lambda env=None: False)
    monkeypatch.setattr(proctree, "visible_chips", lambda: 2)
    cfg = appmod.AppConfig(target="scalable-single-binary", scalable_instances=4)
    with pytest.raises(ValueError, match="shows 2 chips"):
        appmod._prepare_tree(cfg, False)
    # held to the CPU nothing is pinned and the count is the operator's
    monkeypatch.setattr(proctree, "on_cpu", lambda env=None: True)
    before = dict(os.environ)
    appmod._prepare_tree(cfg, False)
    assert cfg.scalable_instances == 4 and dict(os.environ) == before
    assert cfg.worker_concurrency == proctree.TREE_WORKER_CONCURRENCY


def test_dispatch_counters_and_leases_hold_under_threads():
    """Eight threads hand jobs out, post results and lose workers at once:
    no count is lost and no lease outlives its worker."""
    import sys as _sys
    import threading

    from tempo_tpu.services.frontend import Frontend, _Job
    from tempo_tpu.util.kerneltel import TEL

    TEL.reset()
    fe = Frontend.__new__(Frontend)
    fe._lease_lock = threading.Lock()
    fe._leases, fe._lease_workers = {}, {}
    fe._remote_workers, fe._remote_devices, fe._lost_at = {}, {}, {}
    fe._remote_holds = {}
    fe.worker_expiry_s = 60.0
    requeued = []

    class Q:
        def enqueue(self, tenant, job):
            requeued.append(job)

    fe.queue = Q()
    n_threads, per = 8, 400
    prev = _sys.getswitchinterval()
    _sys.setswitchinterval(1e-5)

    def work(k: int):
        w = f"querier-{k}"
        for i in range(per):
            TEL.record_dispatch(w if k % 2 else "local", 0.001)
            jid = f"{k}-{i}"
            with fe._lease_lock:
                fe._remote_workers[w] = time.monotonic()
                fe._remote_devices[w] = {"count": 1}
                fe._remote_holds[w] = frozenset({jid})
                fe._leases[jid] = ([("t", _Job("k", {}, None, ()))],
                                   time.monotonic() + 60, [1])
                fe._lease_workers[jid] = w
            if i % 50 == 49:
                fe.worker_lost(w)

    try:
        ts = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in ts)
    finally:
        _sys.setswitchinterval(prev)
    st = TEL.dispatch_stats()
    assert st["jobs"] == {"local": 4 * per, "remote": 4 * per}
    assert sum(r["jobs"] for r in st["by_worker"].values()) == n_threads * per
    assert st["by_worker"]["local"]["busy_seconds"] == pytest.approx(4 * per * 0.001)
    # every lease of a lost worker went back to the queue, exactly once
    assert fe._leases == {} and fe._lease_workers == {}
    assert len(requeued) == n_threads * per and fe.attached_workers() == {}
    assert fe._remote_holds == {}  # what a lost worker held goes with it
    TEL.reset()


def test_merged_trace_names_every_chip():
    """One profiler file for the tree: instance i's device plane becomes
    /device:TPU:<i>, host planes are instance 0's."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks", "tests"))
    from xspace_writer import encode_xspace

    def space(op, host):
        return encode_xspace([
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": [(op, 100, 50)]}]},
            {"name": "/host:CPU", "lines": [
                {"name": "t", "events": [(host, 90, 80)]}]}])

    merged = proctree.merge_xspaces(
        [space("op0", "tempo/a"), space("op1", "tempo/b"), space("op2", "tempo/c")])
    from jax.profiler import ProfileData

    planes = {p.name: [e.name for ln in p.lines for e in ln.events]
              for p in ProfileData.from_serialized_xspace(merged).planes}
    assert planes == {"/device:TPU:0": ["op0"], "/host:CPU": ["tempo/a"],
                      "/device:TPU:1": ["op1"], "/device:TPU:2": ["op2"]}
    # a file a profiler wrote: a renamed plane keeps every event
    with open(os.path.join(REPO, "benchmarks", "tests", "fixtures",
                           "small.xplane.pb"), "rb") as f:
        real = f.read()

    def events(data, plane):
        return [(ln.name, e.name, e.start_ns, e.duration_ns)
                for p in ProfileData.from_serialized_xspace(data).planes
                if p.name == plane for ln in p.lines for e in ln.events]

    both = proctree.merge_xspaces([real, real])
    assert len(events(real, "/device:TPU:0")) > 100
    assert events(both, "/device:TPU:1") == events(real, "/device:TPU:0")
    assert events(both, "/host:CPU") == events(real, "/host:CPU")
