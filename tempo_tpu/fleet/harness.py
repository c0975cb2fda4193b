"""Fleet harness: launch, torture and certify the multi-process cluster.

`python -m tempo_tpu.fleet.harness --out FLEET_SCALE.json` builds the
N-frontend x M-querier x K-ingester topology as real OS processes over
gossip membership and runs two certifications. It is a CPU harness:
every role is started with JAX_PLATFORMS=cpu, because several
kernel-launching roles share this host and a chip belongs to one
process (on a chip host the operator gives the chip to one role).

1. **QPS scaling 1 -> 4 queriers.**  Every querier worker runs at
   concurrency 1 and every search job carries chaos-injected replica
   latency (`rpc.client` latency rule), so a job costs wall-clock, not
   CPU -- on a single-core box that is exactly the regime where adding
   queriers adds throughput (the fleet's dispatch concurrency is the
   bottleneck being certified, not the host's arithmetic).  The ratio
   of measured QPS at M=4 vs M=1 must clear 3x.

2. **Rolling ingester restart at RF=2 under vulture.**  K ingesters
   are SIGKILLed and respawned in turn -- never two at once -- while
   vulture's find_by_id/search probes run continuously against the
   frontend and pushes flow through the distributor (chaos latency on
   its replica legs the whole time).  Zero miss/corrupt outcomes are
   allowed (sheds OK), and the frontend's read-availability SLO verdict
   must end green.

The artifact records both runs plus the topology, so a regression in
replication, pruning, quorum reads or the sharded poller shows up as a
diffable JSON change.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

# every role is an app process started, waited for and stopped the way
# the scalable-single-binary target starts its own children
from ..services.proctree import (
    REPO_ROOT,
    fetch,
    free_port as _free_port,
    spawn_app,
    stop_procs,
    wait_ready as _wait_ready,
)

# must clear the gossip full-sync cadence (1s) with margin: a live
# replica whose latest heartbeat is still in flight between peers must
# never look dead to the distributor's healthy-set snapshot
HEARTBEAT_TIMEOUT_S = 3.0
# per-job replica latency injected on querier rpc.client legs for the
# scaling run: makes jobs latency-bound so QPS measures fleet dispatch
# concurrency, not single-core arithmetic
JOB_LATENCY_S = 0.08
QUERIER_CHAOS = json.dumps({
    "seed": 7,
    "rules": [{"site": "rpc.client", "action": "latency",
               "latency_s": JOB_LATENCY_S, "p": 1.0}],
})
# the distributor's replica-write legs run with injected latency during
# the rolling restart (chaos active on the WRITE path throughout)
DISTRIBUTOR_CHAOS = json.dumps({
    "seed": 11,
    "rules": [{"site": "rpc.client", "action": "latency",
               "latency_s": 0.005, "p": 0.5}],
})


def _get_json(port: int, path: str, timeout: float = 10.0) -> dict:
    return json.loads(fetch(port, path, timeout=timeout))


class FleetTopology:
    """K ingesters + 1 distributor + 1 query-frontend + M queriers as
    real processes over gossip membership and a shared storage path."""

    def __init__(self, base_dir: str, ingesters: int = 2, queriers: int = 1,
                 rf: int = 2, worker_concurrency: int = 1,
                 querier_chaos: str = "", distributor_chaos: str = "",
                 heartbeat_timeout: float = HEARTBEAT_TIMEOUT_S):
        self.base_dir = base_dir
        self.storage = os.path.join(base_dir, "storage")
        os.makedirs(self.storage, exist_ok=True)
        self.rf = rf
        self.hb = heartbeat_timeout
        self.worker_concurrency = worker_concurrency
        self.querier_chaos = querier_chaos
        self.distributor_chaos = distributor_chaos
        self.ports: dict[str, int] = {}
        self.gports: dict[str, int] = {}
        self.procs: dict[str, subprocess.Popen] = {}
        self.logs: dict[str, object] = {}
        self._ingesters = [f"ing-{i + 1}" for i in range(ingesters)]
        self._queriers = [f"q-{i + 1}" for i in range(queriers)]

    # -------------------------------------------------------- process mgmt
    def _spawn(self, name: str, target: str, extra: tuple = ()) -> None:
        port = self.ports.setdefault(name, _free_port())
        gport = self.gports.setdefault(name, _free_port())
        seed = f"127.0.0.1:{self.gports[self._ingesters[0]]}"
        args = [f"--target={target}", "--http.port", str(port),
                "--storage.path", self.storage,
                "--memberlist.bind", f"127.0.0.1:{gport}",
                "--instance.id", name,
                "--ring.heartbeat-timeout", str(self.hb),
                "--replication.factor", str(self.rf), *extra]
        if name != self._ingesters[0]:
            args += ["--memberlist.join", seed]
        log = open(os.path.join(self.base_dir, f"{name}.log"), "ab")
        self.logs[name] = log
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT)
        env.pop("TEMPO_CHAOS", None)  # only explicit per-role rules
        self.procs[name] = spawn_app(args, env=env, log=log)

    def start(self) -> None:
        for name in self._ingesters:
            self._spawn(name, "ingester")
        for name in self._ingesters:
            _wait_ready(self.ports[name])
        dist_extra = (("--chaos.rules", self.distributor_chaos)
                      if self.distributor_chaos else ())
        self._spawn("dist", "distributor", dist_extra)
        self._spawn("fe", "query-frontend")
        _wait_ready(self.ports["dist"])
        _wait_ready(self.ports["fe"])
        fe_addr = f"http://127.0.0.1:{self.ports['fe']}"
        q_extra = ("--querier.frontend-address", fe_addr,
                   "--querier.worker-concurrency",
                   str(self.worker_concurrency))
        if self.querier_chaos:
            q_extra += ("--chaos.rules", self.querier_chaos)
        for name in self._queriers:
            self._spawn(name, "querier", q_extra)
        for name in self._queriers:
            _wait_ready(self.ports[name])

    def kill_ingester(self, name: str) -> None:
        """SIGKILL: no LEAVE is written; only the heartbeat prune can
        evict the corpse from the write ring."""
        p = self.procs[name]
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=15)
        stop_procs([p])  # reaped: closes its lifeline

    def respawn_ingester(self, name: str) -> None:
        self._spawn(name, "ingester")
        _wait_ready(self.ports[name])

    def stop(self) -> None:
        stop_procs(list(self.procs.values()), grace_s=10.0)
        for log in self.logs.values():
            try:
                log.close()
            except Exception:
                pass

    # ------------------------------------------------------------- helpers
    @property
    def dist_url(self) -> str:
        return f"http://127.0.0.1:{self.ports['dist']}"

    @property
    def fe_url(self) -> str:
        return f"http://127.0.0.1:{self.ports['fe']}"

    def push_traces(self, n: int, seed: int = 5) -> list:
        from ..util.testdata import make_traces
        from ..wire import otlp_json

        traces = make_traces(n, seed=seed, n_spans=4)
        deadline = time.time() + 30
        for i, (_tid, tr) in enumerate(traces):
            body = otlp_json.dumps(tr).encode()
            while True:  # first pushes race the gossip round
                try:
                    req = urllib.request.Request(
                        self.dist_url + "/v1/traces", data=body,
                        headers={"Content-Type": "application/json"})
                    urllib.request.urlopen(req, timeout=15)
                    break
                except urllib.error.HTTPError:
                    if time.time() > deadline:
                        raise
                    time.sleep(0.5)
        return traces

    def chaos_injected(self, name: str) -> int:
        try:
            st = _get_json(self.ports[name], "/status/chaos")
        except Exception:
            return 0
        return int(st.get("injected_total", 0))


# ----------------------------------------------------------- QPS scaling
def measure_qps(fe_url: str, duration_s: float = 12.0, clients: int = 8,
                warmup_s: float = 3.0) -> dict:
    """Closed-loop search load against the frontend: `clients` threads
    each re-issue /api/search as fast as the fleet completes it."""
    stop = threading.Event()
    counts = [0] * clients
    errors = [0] * clients
    started = time.monotonic()
    measure_from = started + warmup_s

    def worker(i: int) -> None:
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                        fe_url + "/api/search?limit=20", timeout=30) as r:
                    r.read()
                if time.monotonic() >= measure_from:
                    counts[i] += 1
            except Exception:
                if time.monotonic() >= measure_from:
                    errors[i] += 1

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    time.sleep(warmup_s + duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    done = sum(counts)
    return {"qps": round(done / duration_s, 2), "requests": done,
            "errors": sum(errors), "clients": clients,
            "duration_s": duration_s}


def run_qps_scaling(base_dir: str, querier_counts=(1, 4),
                    duration_s: float = 12.0) -> dict:
    """One topology per point: same ingesters/frontend shape, only M
    changes. Jobs are latency-bound (chaos) so QPS ∝ fleet concurrency."""
    points = []
    for m in querier_counts:
        topo = FleetTopology(
            os.path.join(base_dir, f"qps-m{m}"), ingesters=2, queriers=m,
            rf=2, worker_concurrency=1, querier_chaos=QUERIER_CHAOS)
        try:
            topo.start()
            topo.push_traces(6, seed=5)
            # one successful search proves the pipeline before timing
            deadline = time.time() + 30
            while True:
                try:
                    urllib.request.urlopen(
                        topo.fe_url + "/api/search?limit=5", timeout=20)
                    break
                except Exception:
                    if time.time() > deadline:
                        raise
                    time.sleep(0.5)
            res = measure_qps(topo.fe_url, duration_s=duration_s)
            res["queriers"] = m
            res["chaos_injected"] = sum(
                topo.chaos_injected(q) for q in topo._queriers)
            points.append(res)
        finally:
            topo.stop()
    base = points[0]["qps"] or 1e-9
    ratio = round(points[-1]["qps"] / base, 2)
    return {
        "job_latency_chaos_s": JOB_LATENCY_S,
        "worker_concurrency": 1,
        "points": points,
        "ratio": ratio,
        "target_ratio": 3.0,
        "pass": ratio >= 3.0 and all(p["errors"] == 0 for p in points),
    }


# ------------------------------------------------------- rolling restart
def run_rolling_restart(base_dir: str, ingesters: int = 3, queriers: int = 2,
                        settle_s: float = 4.0) -> dict:
    """SIGKILL + respawn each ingester in turn at RF=2 while vulture
    find_by_id/search probes run continuously. Zero miss/corrupt allowed."""
    from ..vulture import Vulture, VultureConfig

    topo = FleetTopology(
        os.path.join(base_dir, "rolling"), ingesters=ingesters,
        queriers=queriers, rf=2, worker_concurrency=2,
        distributor_chaos=DISTRIBUTOR_CHAOS)
    outcomes: dict[str, int] = {}
    details: list[str] = []
    stop = threading.Event()

    def vloop(v: Vulture) -> None:
        while not stop.is_set():
            try:
                results = v.cycle()
            except Exception as e:  # a sick probe loop is itself a failure
                outcomes["probe_crash"] = outcomes.get("probe_crash", 0) + 1
                details.append(f"probe loop: {e!r}")
                time.sleep(0.5)
                continue
            for r in results:
                outcomes[r.outcome] = outcomes.get(r.outcome, 0) + 1
                if r.outcome not in ("ok", "shed") and len(details) < 20:
                    details.append(f"{r.family}: {r.outcome} {r.detail}")

    try:
        topo.start()
        topo.push_traces(4, seed=13)  # warm the write path + gossip
        vcfg = VultureConfig(
            push_url=topo.dist_url, query_url=topo.fe_url,
            families=("find_by_id", "search"), flush_every=0,
            generator_probes=False, visibility_timeout_s=25.0,
            spans_per_trace=3, batch_ids=2, seed=3)
        v = Vulture(vcfg)
        vt = threading.Thread(target=vloop, args=(v,), daemon=True)
        vt.start()
        time.sleep(3.0)  # probes flowing before the first kill
        restarts = []
        for name in topo._ingesters:
            t0 = time.time()
            topo.kill_ingester(name)
            # the prune satellite's guarantee: the corpse leaves the
            # write ring within ~one heartbeat interval of the timeout
            time.sleep(topo.hb + 1.0)
            topo.respawn_ingester(name)
            time.sleep(settle_s)  # WAL replay + rejoin settle
            restarts.append({"ingester": name,
                             "outage_s": round(time.time() - t0, 2)})
        time.sleep(3.0)  # post-roll probes against the healed fleet
        stop.set()
        vt.join(timeout=60)
        try:
            slo = _get_json(topo.ports["fe"], "/status/slo")
            ra = slo.get("objectives", {}).get("read-availability", {})
            verdict = ra.get("verdict", slo.get("verdict", "unknown"))
        except Exception:
            verdict = "unknown"
        fleet_view = {}
        try:
            fleet_view = _get_json(topo.ports["dist"], "/status/fleet")
        except Exception:
            pass
        misses = outcomes.get("miss", 0) + outcomes.get("timeout", 0)
        corrupt = outcomes.get("corrupt", 0)
        bad = (misses + corrupt + outcomes.get("error", 0)
               + outcomes.get("probe_crash", 0))
        return {
            "rf": 2,
            "ingesters": ingesters,
            "queriers": queriers,
            "restarts": restarts,
            "probe_families": ["find_by_id", "search"],
            "cycles": v.cycles,
            "outcomes": outcomes,
            "misses": misses,
            "corrupt": corrupt,
            "failure_details": details,
            "chaos": {
                "distributor_injected": topo.chaos_injected("dist"),
            },
            "replication_writes": (fleet_view.get("replication", {})
                                   .get("writes", {})),
            "read_availability_verdict": verdict,
            "pass": bad == 0 and verdict == "ok" and v.cycles > 0,
        }
    finally:
        stop.set()
        topo.stop()


# ------------------------------------------------------------------ main
def certify(out_path: str, base_dir: str, quick: bool = False) -> dict:
    t0 = time.time()
    qps = run_qps_scaling(
        base_dir, querier_counts=(1, 4), duration_s=6.0 if quick else 12.0)
    rolling = run_rolling_restart(
        base_dir, ingesters=2 if quick else 3, queriers=2,
        settle_s=3.0 if quick else 4.0)
    artifact = {
        "schema": "fleet_scale/v1",
        "generated_unix": int(t0),
        "wall_s": round(time.time() - t0, 1),
        "topology": {
            "frontends": 1,
            "distributors": 1,
            "membership": "gossip",
            "ring_heartbeat_timeout_s": HEARTBEAT_TIMEOUT_S,
        },
        "qps_scaling": qps,
        "rolling_restart": rolling,
        "ok": bool(qps["pass"] and rolling["pass"]),
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)
        f.write("\n")
    return artifact


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("tempo-tpu-fleet-harness")
    ap.add_argument("--out", default="FLEET_SCALE.json")
    ap.add_argument("--work-dir", default="",
                    help="scratch dir for storage/logs (default: temp)")
    ap.add_argument("--quick", action="store_true",
                    help="shorter measurement windows / smaller fleet")
    args = ap.parse_args(argv)
    import tempfile

    base = args.work_dir or tempfile.mkdtemp(prefix="tempo-fleet-")
    artifact = certify(args.out, base, quick=args.quick)
    print(json.dumps(artifact, indent=2, sort_keys=True))
    print(f"\nFLEET_SCALE -> {args.out}  ok={artifact['ok']}")
    return 0 if artifact["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
