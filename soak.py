"""Load/soak rig: sustained concurrent write+query against a running
tempo-tpu instance with latency assertions.

The reference drives this with k6 (integration/bench/smoke_test.js:
checked write/read cycles; stress_test_write_path.js: sustained write
load with p95 thresholds). Same contract here, self-contained: N writer
threads push OTLP batches, M reader threads search + read back ids
that were written, for a wall-clock duration; the run FAILS (exit 1)
on any error, any written-then-unfindable trace at the end, or
latency percentiles above thresholds.

Mixed-tenant mode (--tenants N): writers round-robin across N tenants,
readers draw their tenant from a Zipf distribution (--zipf skew, rank 1
hottest) so a few heavy tenants dominate exactly like production read
traffic, and the report carries per-tenant p50/p95/p99 plus per-tenant
429 shed counts -- the harness the cache-affinity/QoS acceptance gates
run on. 429 responses count as sheds (the per-tenant QoS budget doing
its job), not errors.

Run against a live instance:
    python soak.py --target http://localhost:3200 --duration 60
or self-hosted (spawns a single-binary app on an ephemeral port):
    python soak.py --self-host --duration 30
mixed-tenant with QoS overrides:
    python soak.py --self-host --tenants 4 --overrides overrides.yaml
dashboard-shaped repeat traffic (result-cache acceptance):
    python soak.py --self-host --repeat-zipf 1.1 --duration 30
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time
import urllib.error
import urllib.request


def _pct(xs, p):
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * p))]


def _lat_summary(xs) -> dict:
    return {
        "p50_ms": round(_pct(xs, 0.5) * 1e3, 2),
        "p95_ms": round(_pct(xs, 0.95) * 1e3, 2),
        "p99_ms": round(_pct(xs, 0.99) * 1e3, 2),
        "n": len(xs),
    }


class Soak:
    def __init__(self, target: str, writers: int, readers: int,
                 spans_per_trace: int = 8, batch: int = 5,
                 tenants: list[str] | None = None, zipf: float = 1.2,
                 live_tail: bool = False, query_target: str = "",
                 repeat_zipf: float = 0.0):
        self.target = target.rstrip("/")
        # split-role fleets write to the distributor and read from the
        # query-frontend; "" = one process serves both (today's default)
        self.query_target = (query_target or target).rstrip("/")
        self.writers = writers
        self.readers = readers
        self.spans_per_trace = spans_per_trace
        self.batch = batch
        # live-tail mode: searches ask for the most recent window only
        # (start=now-60s), the recent-data shape the live-head device
        # engine serves from the ingester's staged columns
        self.live_tail = live_tail
        # "" = single-tenant (no X-Scope-OrgID header), today's default
        self.tenants: list[str] = list(tenants) if tenants else [""]
        # Zipf read skew over tenant rank: weight 1/(rank+1)^s
        self.zipf_weights = [1.0 / (i + 1) ** zipf
                             for i in range(len(self.tenants))]
        self.lock = threading.Lock()
        self.written: dict[str, list[str]] = {t: [] for t in self.tenants}
        self.errors: list[str] = []
        self.write_lat: dict[str, list[float]] = {t: [] for t in self.tenants}
        self.search_lat: dict[str, list[float]] = {t: [] for t in self.tenants}
        self.find_lat: dict[str, list[float]] = {t: [] for t in self.tenants}
        self.sheds: dict[str, int] = {t: 0 for t in self.tenants}  # 429s
        self.found = 0
        self.not_yet = 0  # reads that raced ingest (retried at the end)
        # --repeat-zipf: dashboard-shaped read traffic -- a FIXED pool
        # of query templates drawn Zipf(s) by rank, so the same few
        # queries repeat exactly like auto-refreshing dashboard panels
        # and the result cache has something to hit. Each response is
        # classified by its X-Tempo-Cache header.
        self.repeat_zipf = repeat_zipf
        self.cache_lat: dict[str, list[float]] = {
            k: [] for k in ("hit", "extend", "miss", "off")}
        if repeat_zipf > 0:
            t0 = int(time.time())

            def hist(svc: str, off_s: int):
                # immutable historical window: end sits behind the
                # live window, so only a blocklist change invalidates
                return lambda: (f"/api/search?tags=service.name%3D{svc}"
                                f"&limit=20&start={t0 - off_s}&end={t0 - 60}")

            def edge(svc: str):
                # moving now-edge window: the auto-refresh panel shape
                # the incremental-extension path exists for
                def f():
                    now = int(time.time())
                    return (f"/api/search?tags=service.name%3D{svc}"
                            f"&limit=20&start={now - 600}&end={now}")
                return f

            self._qtemplates = (
                [hist(f"soak-svc-{i}", 3600) for i in range(4)]
                + [hist(f"soak-svc-{i}", 1800) for i in range(4)]
                + [edge("soak-svc-0"), edge("soak-svc-1")])
            self._qweights = [1.0 / (r + 1) ** repeat_zipf
                              for r in range(len(self._qtemplates))]

    def _headers(self, tenant: str, ctype: str = "") -> dict:
        h = {}
        if ctype:
            h["Content-Type"] = ctype
        if tenant:
            h["X-Scope-OrgID"] = tenant
        return h

    def _post(self, path: str, body: bytes, ctype="application/json",
              tenant: str = ""):
        req = urllib.request.Request(self.target + path, data=body,
                                     headers=self._headers(tenant, ctype))
        with urllib.request.urlopen(req, timeout=15) as r:
            return r.read()

    def _get(self, path: str, tenant: str = ""):
        req = urllib.request.Request(self.query_target + path,
                                     headers=self._headers(tenant))
        with urllib.request.urlopen(req, timeout=15) as r:
            return r.read()

    def _get_with_cache_header(self, path: str, tenant: str = ""):
        """GET returning (body, X-Tempo-Cache header) -- "" when the
        result cache is disabled or the target predates it."""
        req = urllib.request.Request(self.query_target + path,
                                     headers=self._headers(tenant))
        with urllib.request.urlopen(req, timeout=15) as r:
            return r.read(), r.headers.get("X-Tempo-Cache", "")

    def _pick_tenant(self, rng: random.Random) -> str:
        if len(self.tenants) == 1:
            return self.tenants[0]
        return rng.choices(self.tenants, weights=self.zipf_weights)[0]

    def _trace_json(self, tid_hex: str, svc: str) -> dict:
        now = time.time_ns()
        spans = []
        for i in range(self.spans_per_trace):
            spans.append({
                "traceId": tid_hex,
                "spanId": os.urandom(8).hex(),
                "parentSpanId": spans[0]["spanId"] if spans else "",
                "name": f"op-{i % 4}",
                "startTimeUnixNano": str(now + i * 1000),
                "endTimeUnixNano": str(now + i * 1000 + 2_000_000),
                "attributes": [{"key": "i", "value": {"intValue": str(i)}}],
            })
        return {"resourceSpans": [{
            "resource": {"attributes": [
                {"key": "service.name", "value": {"stringValue": svc}}]},
            "scopeSpans": [{"scope": {"name": "soak"}, "spans": spans}],
        }]}

    def _writer(self, stop: threading.Event, wid: int):
        svc = f"soak-svc-{wid % 4}"
        tenant = self.tenants[wid % len(self.tenants)]
        # alternate transports: even writers push OTLP-proto (the raw
        # native-scan fast path, the production OTel transport), odd
        # writers push OTLP-JSON (the model path) -- the soak hammers
        # both write paths concurrently
        use_proto = wid % 2 == 0
        if use_proto:
            try:
                from tempo_tpu.wire import otlp_json, otlp_pb
            except ImportError as e:
                # --target mode may run where the package isn't importable;
                # a writer dying silently would pass the soak vacuously
                with self.lock:
                    self.errors.append(f"write: proto transport unavailable: {e}")
                return
        while not stop.is_set():
            ids = [os.urandom(16).hex() for _ in range(self.batch)]
            try:
                # bodies built BEFORE the timed window: write_lat measures
                # the POSTs, not client-side encoding
                bodies = []
                for tid in ids:
                    j = json.dumps(self._trace_json(tid, svc)).encode()
                    if use_proto:
                        bodies.append((otlp_pb.encode_trace(otlp_json.loads(j)),
                                       "application/x-protobuf"))
                    else:
                        bodies.append((j, "application/json"))
                t0 = time.perf_counter()
                posted, shed = [], 0
                for tid, (body, ctype) in zip(ids, bodies):
                    try:
                        self._post("/v1/traces", body, ctype=ctype,
                                   tenant=tenant)
                        posted.append(tid)
                    except urllib.error.HTTPError as e:
                        # an ingest-side 429 (rate limit from the same
                        # overrides file) is a shed doing its job, not a
                        # soak failure -- and its fast-fail must not
                        # enter the write percentiles
                        if e.code != 429:
                            raise
                        shed += 1
                dt = (time.perf_counter() - t0) / self.batch
                with self.lock:
                    if not shed:
                        self.write_lat[tenant].append(dt)
                    self.sheds[tenant] += shed
                    self.written[tenant].extend(posted)
            except Exception as e:
                with self.lock:
                    self.errors.append(f"write[{tenant}]: {type(e).__name__}: {e}")
                return

    def _reader(self, stop: threading.Event, rid: int):
        rng = random.Random(0x50AC + rid)
        while not stop.is_set():
            tenant = self._pick_tenant(rng)
            with self.lock:
                ids = self.written[tenant]
                tid = rng.choice(ids) if ids else None
            try:
                # a 429 shed is counted but its (fast-fail) latency is
                # NOT: percentiles must measure served reads, or a
                # mostly-shed tenant would report flattering numbers
                if tid is not None:
                    t0 = time.perf_counter()
                    shed = False
                    try:
                        self._get(f"/api/traces/{tid}", tenant=tenant)
                        with self.lock:
                            self.found += 1
                    except urllib.error.HTTPError as e:
                        if e.code == 429:  # QoS shed-load: counted, not fatal
                            shed = True
                            with self.lock:
                                self.sheds[tenant] += 1
                        elif e.code != 404:
                            raise
                        else:
                            with self.lock:  # raced ingest; re-checked at the end
                                self.not_yet += 1
                    if not shed:
                        with self.lock:
                            self.find_lat[tenant].append(time.perf_counter() - t0)
                outcome = None
                if self.repeat_zipf > 0:
                    path = rng.choices(self._qtemplates,
                                       weights=self._qweights)[0]()
                else:
                    path = "/api/search?tags=service.name%3Dsoak-svc-1&limit=20"
                    if self.live_tail:
                        now = int(time.time())
                        path += f"&start={now - 60}&end={now + 5}"
                t0 = time.perf_counter()
                shed = False
                try:
                    if self.repeat_zipf > 0:
                        _body, hdr = self._get_with_cache_header(
                            path, tenant=tenant)
                        outcome = hdr if hdr in ("hit", "extend", "miss") \
                            else "off"
                    else:
                        self._get(path, tenant=tenant)
                except urllib.error.HTTPError as e:
                    if e.code != 429:
                        raise
                    shed = True
                    with self.lock:
                        self.sheds[tenant] += 1
                if not shed:
                    dt = time.perf_counter() - t0
                    with self.lock:
                        self.search_lat[tenant].append(dt)
                        if outcome is not None:
                            self.cache_lat[outcome].append(dt)
            except Exception as e:
                with self.lock:
                    self.errors.append(f"read[{tenant}]: {type(e).__name__}: {e}")
                return
            time.sleep(0.01)

    def run(self, duration_s: float, settle_s: float = 5.0,
            max_write_p95_s: float = 1.0, max_search_p95_s: float = 3.0,
            sample_verify: int = 50) -> dict:
        stop = threading.Event()
        threads = [threading.Thread(target=self._writer, args=(stop, i), daemon=True)
                   for i in range(self.writers)]
        threads += [threading.Thread(target=self._reader, args=(stop, i), daemon=True)
                    for i in range(self.readers)]
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=20)

        time.sleep(settle_s)  # let live traces become queryable
        missing = []
        verified = 0
        per_tenant_verify = max(1, sample_verify // len(self.tenants))
        for tenant in self.tenants:
            sample = random.sample(self.written[tenant],
                                   min(per_tenant_verify, len(self.written[tenant])))
            verified += len(sample)
            for tid in sample:
                try:
                    self._get(f"/api/traces/{tid}", tenant=tenant)
                except Exception:
                    missing.append(tid)

        all_writes = [x for xs in self.write_lat.values() for x in xs]
        all_search = [x for xs in self.search_lat.values() for x in xs]
        all_find = [x for xs in self.find_lat.values() for x in xs]
        report = {
            "written": sum(len(v) for v in self.written.values()),
            "found_live": self.found,
            "raced_reads": self.not_yet,
            "errors": self.errors[:5],
            "error_count": len(self.errors),
            "write_p50_ms": round(_pct(all_writes, 0.5) * 1e3, 2),
            "write_p95_ms": round(_pct(all_writes, 0.95) * 1e3, 2),
            "search_p50_ms": round(_pct(all_search, 0.5) * 1e3, 2),
            "search_p95_ms": round(_pct(all_search, 0.95) * 1e3, 2),
            "search_p99_ms": round(_pct(all_search, 0.99) * 1e3, 2),
            "find_p50_ms": round(_pct(all_find, 0.5) * 1e3, 2),
            "sheds_429": sum(self.sheds.values()),
            "verified_sample": verified,
            "missing_after_settle": missing,
        }
        if len(self.tenants) > 1:
            # per-tenant QoS/affinity view: rank order == Zipf weight
            # order, so tenants[0] is the heavy tenant by construction
            report["tenants"] = {
                t or "single-tenant": {
                    "written": len(self.written[t]),
                    "sheds_429": self.sheds[t],
                    "search": _lat_summary(self.search_lat[t]),
                    "find": _lat_summary(self.find_lat[t]),
                    "write": _lat_summary(self.write_lat[t]),
                }
                for t in self.tenants
            }
        report["ok"] = (
            not self.errors
            and not missing
            and report["written"] > 0
            and _pct(all_writes, 0.95) <= max_write_p95_s
            and _pct(all_search, 0.95) <= max_search_p95_s
        )
        if self.repeat_zipf > 0:
            hits, ext = self.cache_lat["hit"], self.cache_lat["extend"]
            misses, off = self.cache_lat["miss"], self.cache_lat["off"]
            total = len(hits) + len(ext) + len(misses)
            cached = hits + ext
            report["result_cache"] = {
                "enabled": total > 0,  # 0 classified = kill switch off
                "requests": total + len(off),
                "hits": len(hits),
                "extensions": len(ext),
                "misses": len(misses),
                "uncached": len(off),
                "hit_rate": round(len(cached) / total, 3) if total else 0.0,
                "cached_p50_ms": round(_pct(cached, 0.5) * 1e3, 3),
                "cached_p95_ms": round(_pct(cached, 0.95) * 1e3, 3),
                "fresh_p50_ms": round(_pct(misses, 0.5) * 1e3, 2),
            }
            # the acceptance gate: dashboard-shaped traffic must
            # mostly hit (>= 50%) -- but only when the cache is on
            # (a kill-switch run measures the baseline, not the cache)
            if total >= 20 and len(cached) / total < 0.5:
                report["ok"] = False
                self.errors.append(
                    f"result_cache: hit rate {len(cached) / total:.2f} "
                    f"< 0.5 under repeat-zipf traffic")
                report["errors"] = self.errors[:5]
                report["error_count"] = len(self.errors)
        return report


# the default --chaos mix: transient backend 5xx at 5% plus a little
# injected RPC latency -- the faults the resilience plane (retries,
# hedging, shard degradation, breaker half-open) exists to mask. The
# soak must still pass end to end with this active.
DEFAULT_CHAOS_SPEC = json.dumps({
    "seed": 1,
    "rules": [
        {"site": "backend.read", "action": "error", "p": 0.05},
        {"site": "rpc.client", "action": "latency", "latency_s": 0.02,
         "p": 0.1},
    ],
})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("tempo-tpu-soak")
    ap.add_argument("--target", default="", help="base URL of a running instance")
    ap.add_argument("--query-target", default="",
                    help="base URL reads go to (fleet topologies: the "
                         "query-frontend; '' = same as --target)")
    ap.add_argument("--self-host", action="store_true",
                    help="spawn a single-binary app for the run")
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--writers", type=int, default=4)
    ap.add_argument("--readers", type=int, default=2)
    ap.add_argument("--tenants", type=int, default=1,
                    help="mixed-tenant mode: N tenants, Zipf-skewed reads")
    ap.add_argument("--zipf", type=float, default=1.2,
                    help="Zipf skew exponent for mixed-tenant read traffic")
    ap.add_argument("--overrides", default="",
                    help="per-tenant overrides YAML for the self-hosted app "
                         "(QoS budgets, limits)")
    ap.add_argument("--live-tail", action="store_true",
                    help="searches query only the most recent 60s window "
                         "(exercises the live-head device engine)")
    ap.add_argument("--repeat-zipf", type=float, default=0.0, metavar="S",
                    help="dashboard-shaped reads: draw searches from a "
                         "fixed template pool Zipf(S)-skewed by rank "
                         "(incl. a moving now-edge window), classify "
                         "each response by its X-Tempo-Cache header and "
                         "report result-cache hit rate + cached p50; "
                         "hit rate < 0.5 fails the run when the cache "
                         "is on")
    ap.add_argument("--vulture", action="store_true",
                    help="run the continuous-verification prober beside "
                         "the soak; its SLO verdicts + freshness "
                         "percentiles fold into the summary (probe "
                         "failures fail the run)")
    ap.add_argument("--vulture-interval", type=float, default=2.0)
    ap.add_argument("--generator", action="store_true",
                    help="fold generated-series freshness verdicts into "
                         "the summary: runs the vulture sidecar (if not "
                         "already on) with its span_metrics / "
                         "service_graph probe families and reports the "
                         "push->series-visible percentiles, the "
                         "series_visible SLO verdict and the target's "
                         "generator plane counters; generator probe "
                         "failures or a critical freshness SLO fail "
                         "the run")
    ap.add_argument("--chaos", nargs="?", const=DEFAULT_CHAOS_SPEC,
                    default="", metavar="SPEC",
                    help="run the soak under fault injection: SPEC is "
                         "inline JSON rules / a rules file for "
                         "TEMPO_CHAOS (bare --chaos = a transient 5%% "
                         "backend-fault + RPC-latency mix the retry/"
                         "hedge/breaker armor must mask); self-host "
                         "only -- the env reaches the spawned app")
    ap.add_argument("--write-p95", type=float, default=1.0)
    ap.add_argument("--search-p95", type=float, default=3.0)
    args = ap.parse_args(argv)

    tenants = ([f"soak-tenant-{i}" for i in range(args.tenants)]
               if args.tenants > 1 else None)

    proc = None
    target = args.target
    if args.self_host or not target:
        import subprocess
        import tempfile

        port = random.randint(20000, 40000)
        d = tempfile.mkdtemp(prefix="soak-")
        cmd = [sys.executable, "-m", "tempo_tpu.services.app", "--target=all",
               f"--storage.path={d}", f"--http.port={port}"]
        if tenants:
            cmd.append("--multitenancy")
        if args.overrides:
            cmd.append(f"--overrides.path={args.overrides}")
        # the environment passes through untouched: the server child is
        # the one process that touches jax, on whatever device jax finds
        # (it refuses to start on no chip unless JAX_PLATFORMS=cpu)
        env = dict(os.environ)
        if args.chaos:
            env["TEMPO_CHAOS"] = args.chaos
        proc = subprocess.Popen(cmd, env=env)
        target = f"http://127.0.0.1:{port}"
        for _ in range(600):
            if proc.poll() is not None:
                sys.exit(f"soak: self-hosted server exited {proc.returncode} "
                         "before becoming ready")
            try:
                urllib.request.urlopen(target + "/ready", timeout=1)
                break
            except OSError:
                time.sleep(0.2)

    # vulture sidecar: black-box probes of every read path WHILE the
    # soak hammers the instance -- the combination the prober exists
    # for (correctness under load, not at rest). Runs in its own
    # thread against the same target/tenant.
    vult = vstop = vthread = None
    if args.vulture or args.generator:
        from tempo_tpu.vulture import Vulture, VultureConfig

        # Vulture itself disables cold-read /flush probes for remote
        # tokenless targets (loopback-trust guard), so a remote soak
        # still runs every other family
        vult = Vulture(VultureConfig(
            push_url=target, query_url=target,
            tenant=tenants[0] if tenants else "",
            visibility_timeout_s=10.0, flush_every=2, seed=1))
        vstop = threading.Event()

        def vloop():
            while not vstop.is_set():
                try:
                    vult.cycle()
                except Exception:  # a dying sidecar must not kill the soak
                    pass
                vstop.wait(args.vulture_interval)

        vthread = threading.Thread(target=vloop, daemon=True,
                                   name="soak-vulture")
        vthread.start()

    try:
        dev = None
        if proc is not None:
            with urllib.request.urlopen(target + "/status/kernels") as r:
                dev = json.load(r)["device"]
            print(f"soak: self-hosted server on platform={dev['platform']} "
                  f"device_kind={dev['device_kind']!r} count={dev['count']}",
                  file=sys.stderr)
        soak = Soak(target, args.writers, args.readers, tenants=tenants,
                    zipf=args.zipf, live_tail=args.live_tail,
                    query_target=args.query_target,
                    repeat_zipf=args.repeat_zipf)
        report = soak.run(args.duration, max_write_p95_s=args.write_p95,
                          max_search_p95_s=args.search_p95)
        report["device"] = dev  # None = remote target, not asked
        if vult is not None:
            vstop.set()
            vthread.join(timeout=30)
            vs = vult.status()
            bad = sum(n for fam in vs["outcomes"].values()
                      for out, n in fam.items()
                      if out not in ("ok", "shed"))
            report["vulture"] = {
                "cycles": vs["cycles"],
                "probe_failures": bad,
                "outcomes": vs["outcomes"],
                "freshness": vs["freshness"],
                "slo_verdict": vs["slo"].get("verdict", "ok"),
                "slo": {name: {"verdict": o.get("verdict"),
                               "burn_rates": o.get("burn_rates")}
                        for name, o in vs["slo"].get("objectives", {}).items()},
                "failures": vs["failures"][:5],
            }
            report["ok"] = bool(report["ok"]) and bad == 0
            if args.generator:
                # series-freshness verdicts: the vulture generator
                # families' outcomes + the series_visible SLO beside
                # the target's own generator plane counters, so one
                # soak summary answers "are generated series fresh
                # and correct UNDER this load"
                fams = {f: vs["outcomes"].get(f, {})
                        for f in ("span_metrics", "service_graph")}
                gen_bad = sum(n for fam in fams.values()
                              for out, n in fam.items()
                              if out not in ("ok", "shed"))
                slo_obj = vs["slo"].get("objectives", {}).get(
                    "freshness-series_visible", {})
                try:
                    ks = json.loads(urllib.request.urlopen(
                        target + "/status/kernels", timeout=10).read())
                    tgt = ks.get("generator", {})
                except Exception:
                    tgt = {}
                report["generator"] = {
                    "series_freshness": vs["freshness"].get(
                        "series_visible", {}),
                    "slo_verdict": slo_obj.get("verdict"),
                    "burn_rates": slo_obj.get("burn_rates"),
                    "outcomes": fams,
                    "probe_failures": gen_bad,
                    "target": {k: tgt.get(k) for k in (
                        "windows", "window_spans", "edges_completed",
                        "unpaired", "expired", "freshness_avg_s",
                        "freshness_max_s")},
                }
                report["ok"] = (bool(report["ok"]) and gen_bad == 0
                                and slo_obj.get("verdict") != "critical")
        if args.chaos:
            # the proof artifact: how many faults were actually
            # injected (a chaos soak that injected nothing proves
            # nothing) next to the retry/hedge/breaker counters that
            # absorbed them
            if proc is None:
                print("soak: --chaos only arms a --self-host app; the "
                      "remote target keeps its own TEMPO_CHAOS",
                      file=sys.stderr)
            try:
                st = json.loads(urllib.request.urlopen(
                    target + "/status/chaos", timeout=10).read())
                report["chaos"] = {
                    "enabled": st.get("enabled", False),
                    "injected_total": st.get("injected_total", 0),
                    "retries": st.get("retries", {}),
                    "hedging": st.get("hedging", {}),
                    "breakers": {leg: b.get("state")
                                 for leg, b in st.get("breakers", {}).items()},
                }
                if proc is not None and not st.get("injected_total"):
                    report["ok"] = False
                    report.setdefault("errors", []).append(
                        "chaos: plane armed but zero faults injected")
            except Exception as e:
                report["chaos"] = {"error": f"{type(e).__name__}: {e}"}
        print(json.dumps(report, indent=2))
        return 0 if report["ok"] else 1
    finally:
        if vstop is not None:
            vstop.set()
        if proc is not None:
            proc.terminate()


if __name__ == "__main__":
    sys.exit(main())
