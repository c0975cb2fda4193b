#!/usr/bin/env python3
"""chip_smoke.py -- the quickest proof that tempo-tpu still starts,
compiles and answers correctly on a directly attached TPU.

    python chip_smoke.py                 # one chip, one full-width block
    python chip_smoke.py --chips 4       # a four-chip host, four blocks
    JAX_PLATFORMS=cpu python chip_smoke.py --allow-cpu --scale tiny   # dry run

What it does, through the entry points a user would call:

  1. builds the native codec library from native/vtpu_native.cc;
  2. writes a corpus from --seed, dated now (the last full clock hour):
     by default ONE block at the reference's documented shape
     (BASELINE.md: ~150 K traces, 10.4 M spans) -- 150,000 traces x 69
     spans, 100 attribute keys, 5,000 values, 64 services, 512 span
     names;
  3. starts `python -m tempo_tpu.services.app --target=all` as its ONE
     child with the environment untouched, and refuses to go on unless
     the server says it runs on a TPU whose device_kind the peaks table
     knows;
  4. pushes traces over OTLP/HTTP, reads them back live, flushes, reads
     them back from the cut block, searches / finds / runs TraceQL and
     TraceQL metrics over the full-width block, fires one concurrent
     burst -- every answer compared with a plain numpy oracle computed
     here from the generated columns;
  5. checks the kernel, routing, compile-cache and native status the
     server reports, restarts the server on the same directories and
     re-asks one request of each shape.

This process never imports jax: it owns no chip, the server child does.
Timings printed here are smoke timings of single requests, compile
included -- not benchmark numbers.

Stdout: the one-line JSON summary (ends with `"claim": null`), then as the
LAST line exactly `{"ok": true|false, "device": {"platform": ..., "kind":
..., "count": N}}` with the device as the server's jax reports it. Exit
code 0 only when every check passed on a TPU (or, with --allow-cpu, on
the CPU: a `CPU DRY RUN` line and `"cpu_dry_run": true` in the summary,
never a chip result). With no server on an accelerator nothing is printed
on stdout at all.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.parse

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
TENANT = "single-tenant"  # services/app.DEFAULT_TENANT (multitenancy off)

# ops that must have launched on the chip by the end of the first server's
# life (ISSUE 21): the read path's filter/select/timeseries, the live
# engine, the block cut and the generator's reduce; on one chip also the
# burst's fused launch, on several the mesh programs for trace-by-id
# (parallel/find) and for the two small cut blocks that share a search
# job (search_blocks_device -> parallel/search)
REQUIRED_OPS = ("filter", "select", "timeseries", "live_filter",
                "cut_bloom", "reduce")
REQUIRED_OPS_ONE_CHIP = ("multiquery", "mq_select")
REQUIRED_OPS_MESH = ("mesh_find", "mesh_search")
# the generator's fold follows the measured link round trip against a
# 2 ms constant (ops/reduce), and a directly attached v5e measures
# 1.3-2.0 ms: either engine is a default route there, so `reduce` is
# required only when the router chose the device
ROUTED_BY_LINK_RTT = {"reduce": "spanmetrics"}
FUSED_OPS = ("multiquery", "mesh_multiquery")
MAX_BURSTS = 4
BAD_ROUTING_REASONS = ("fused_error", "engine_init_failed")

SCALES = {
    # traces, spans/trace per block; pushed traces, spans/trace, values
    # of the pushed `smoke.bucket` tag
    "full": dict(traces=150_000, spans_per=69, push_traces=8_000,
                 push_spans=16, push_buckets=400),
    "tiny": dict(traces=1_500, spans_per=8, push_traces=300,
                 push_spans=4, push_buckets=15),
}


class SmokeFailure(Exception):
    """A condition that ends the run at once (no server to talk to)."""


# ----------------------------------------------------------------- checks
class Checks:
    """Every comparison lands here; the run fails if any did."""

    def __init__(self):
        self.passed = 0
        self.failures: list[str] = []
        self._lock = threading.Lock()

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        with self._lock:
            if ok:
                self.passed += 1
            else:
                self.failures.append(f"{name}: {detail}" if detail else name)
                print(f"[smoke] FAIL {name}: {detail}", file=sys.stderr,
                      flush=True)
        return ok

    def equal_sets(self, name: str, got, want) -> bool:
        got, want = set(got), set(want)
        return self.check(
            name, got == want,
            f"got {len(got)} want {len(want)}; missing "
            f"{sorted(want - got)[:3]} extra {sorted(got - want)[:3]}")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------- http
class Client:
    """One keep-alive connection; every request is timed and any 5xx is a
    recorded failure."""

    def __init__(self, port: int, checks: Checks, timings: list):
        self.port, self.checks, self.timings = port, checks, timings
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)

    def request(self, method: str, path: str, body: bytes | None = None,
                ctype: str = "", label: str = ""):
        headers = {"Content-Type": ctype} if ctype else {}

        def once():
            self.conn.request(method, path, body=body, headers=headers)
            resp = self.conn.getresponse()
            return resp, resp.read()

        t0 = time.perf_counter()
        try:
            resp, data = once()
        except (OSError, http.client.HTTPException):
            # one reconnect: the server closes idle keep-alives
            self.conn.close()
            self.conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                   timeout=900)
            resp, data = once()
        dt = time.perf_counter() - t0
        if label:
            self.timings.append({"request": label,
                                 "smoke_wall_s": round(dt, 4),
                                 "status": resp.status})
        self.checks.check(f"http {label or path}", resp.status < 500,
                          f"HTTP {resp.status}: {data[:300]!r}")
        return resp.status, data

    def get_json(self, path: str, label: str = ""):
        status, data = self.request("GET", path, label=label)
        return status, (json.loads(data) if status == 200 else None)


def q(params: dict) -> str:
    return urllib.parse.urlencode(params)


def search_ids(cl: Client, params: dict, label: str) -> list[str] | None:
    status, out = cl.get_json("/api/search?" + q(params), label=label)
    if not cl.checks.check(f"{label} status", status == 200, f"HTTP {status}"):
        return None
    return [t["traceID"].rjust(32, "0") for t in out["traces"]]


# ------------------------------------------------------------ block oracle
class BlockOracle:
    """Plain numpy answers over the columns synth_columns generated --
    independent of the block format, the readers and every kernel."""

    def __init__(self, cols: dict, strings: list[str], ids, spans_per: int):
        self.code = {s: i for i, s in enumerate(strings)}
        self.strings = strings
        self.ids = ids
        self.spans_per = spans_per
        self.n_traces = ids.shape[0]
        keep = ("span.trace_sid", "span.dur_us", "span.res_idx",
                "span.start_ms", "span.start_ns", "span.end_ns", "span.id",
                "span.name_id", "res.service_id", "sattr.span",
                "sattr.key_id", "sattr.str_id")
        self.c = {k: cols[k] for k in keep}
        self.base_ms = int(cols["span.start_ns"].min()) // 1_000_000
        self.start_s = int(cols["span.start_ns"].min()) // 1_000_000_000
        self.end_s = int(cols["span.end_ns"].max()) // 1_000_000_000 + 1

    def hex_ids(self, sids) -> set[str]:
        return {self.ids[int(s)].tobytes().hex() for s in sids}

    def _traces_of(self, span_mask) -> set[str]:
        return self.hex_ids(np.unique(self.c["span.trace_sid"][span_mask]))

    def service_spans(self, svc: str):
        c = self.c
        return c["res.service_id"][c["span.res_idx"]] == self.code[svc]

    def attr_spans(self, key: str, val: str):
        c = self.c
        hit = ((c["sattr.key_id"] == self.code[key])
               & (c["sattr.str_id"] == self.code[val]))
        mask = np.zeros(c["span.trace_sid"].shape[0], bool)
        mask[c["sattr.span"][hit]] = True
        return mask

    def traces_service(self, svc: str) -> set[str]:
        return self._traces_of(self.service_spans(svc))

    def traces_attr(self, key: str, val: str) -> set[str]:
        return self._traces_of(self.attr_spans(key, val))

    def traces_duration_gt(self, us: int) -> set[str]:
        return self._traces_of(self.c["span.dur_us"] > us)

    def traces_descendant(self, key: str, val: str, us: int) -> set[str]:
        """{ attr } >> { duration > us }: spans are a chain per trace
        (span k's parent is span k-1), so the descendants of a span are
        the later spans of its trace."""
        c = self.c
        n = c["span.trace_sid"].shape[0]
        pos = np.arange(n) % self.spans_per
        lhs = self.attr_spans(key, val)
        first = np.full(self.n_traces, self.spans_per, np.int64)
        np.minimum.at(first, c["span.trace_sid"][lhs], pos[lhs])
        rhs = (c["span.dur_us"] > us) & (pos > first[c["span.trace_sid"]])
        return self._traces_of(rhs)

    def rate_counts(self, svc: str, start_ms: int, step_ms: int, nb: int):
        """Spans of `svc` per step bucket, by the block format's own
        millisecond start column (span.start_ms, relative to the block's
        first span)."""
        m = self.service_spans(svc)
        abs_ms = self.base_ms + self.c["span.start_ms"][m].astype(np.int64)
        b = (abs_ms - start_ms) // step_ms
        b = b[(b >= 0) & (b < nb)]
        return np.bincount(b, minlength=nb)[:nb]

    def trace_spans(self, sid: int) -> set[tuple]:
        c = self.c
        lo, hi = sid * self.spans_per, (sid + 1) * self.spans_per
        return {(c["span.id"][i].tobytes().hex(),
                 self.strings[int(c["span.name_id"][i])],
                 int(c["span.start_ns"][i]), int(c["span.end_ns"][i]))
                for i in range(lo, hi)}


def search_window(oracles: list[BlockOracle]) -> dict:
    """start/end (unix seconds) covering every block's traces."""
    return {"start": min(o.start_s for o in oracles) - 60,
            "end": max(o.end_s for o in oracles) + 60}


def spans_of_otlp_json(doc: dict) -> set[tuple]:
    out = set()
    for rs in doc.get("resourceSpans", []):
        for ss in rs.get("scopeSpans", []):
            for sp in ss.get("spans", []):
                out.add((sp["spanId"], sp["name"],
                         int(sp["startTimeUnixNano"]),
                         int(sp["endTimeUnixNano"])))
    return out


# ----------------------------------------------------------- pushed corpus
def make_push_corpus(seed: int, sc: dict, now_ns: int,
                     n_traces: int | None = None) -> list[dict]:
    """Deterministic traces for the write path: one resource per trace,
    a chain of spans, a `smoke.bucket` tag shared by a known few."""
    rnd = random.Random(f"{seed}-push-{n_traces}")
    out = []
    for t in range(n_traces or sc["push_traces"]):
        tid = rnd.getrandbits(128).to_bytes(16, "big")
        t0 = now_ns - 20_000_000_000 + rnd.randrange(0, 10_000_000_000)
        spans = []
        for k in range(sc["push_spans"]):
            start = t0 + k * 1_000_000
            spans.append((rnd.getrandbits(64).to_bytes(8, "big"),
                          f"smoke-op-{k:02d}", start,
                          start + rnd.randrange(1_000_000, 400_000_000)))
        out.append({"id": tid, "service": f"smoke-svc-{t % 8}",
                    "bucket": f"b-{t % sc['push_buckets']:04d}",
                    "spans": spans})
    return out


def encode_push(batch: list[dict]) -> bytes:
    from tempo_tpu.wire.model import (Resource, ResourceSpans, Scope,
                                      ScopeSpans, Span, Trace)
    from tempo_tpu.wire.otlp_pb import encode_trace

    req = Trace()
    for tr in batch:
        ss = ScopeSpans(scope=Scope(name="chip-smoke", version="1"))
        prev = b""
        for k, (sid, name, start, end) in enumerate(tr["spans"]):
            ss.spans.append(Span(
                trace_id=tr["id"], span_id=sid, parent_span_id=prev,
                name=name, kind=2 if k == 0 else 1,
                start_unix_nano=start, end_unix_nano=end,
                attrs={"smoke.bucket": tr["bucket"], "smoke.seq": k}))
            prev = sid
        req.resource_spans.append(ResourceSpans(
            resource=Resource(attrs={"service.name": tr["service"]}),
            scope_spans=[ss]))
    return encode_trace(req)


# ------------------------------------------------------------------ server
class Server:
    def __init__(self, storage: str, log_path: str):
        self.storage, self.log_path = storage, log_path
        self.proc: subprocess.Popen | None = None
        self.port = 0

    def start(self) -> float:
        if not self.port:
            # the restart keeps the port: the server's instance id, and
            # with it the WAL directory it replays, derive from it
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                self.port = s.getsockname()[1]
        t0 = time.perf_counter()
        with open(self.log_path, "ab") as logf:
            # the normal entry point, environment passed through as is:
            # jax in the child picks whatever device this machine has
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "tempo_tpu.services.app",
                 "--target=all", "--storage.path", self.storage,
                 "--http.port", str(self.port)],
                cwd=REPO, stdout=logf, stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited {self.proc.returncode} before /ready:\n"
                    + self.log_tail())
            try:
                conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                  timeout=2)
                conn.request("GET", "/ready")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return time.perf_counter() - t0
            except OSError:
                pass
            time.sleep(0.25)
        raise SmokeFailure("server not ready after 300 s:\n" + self.log_tail())

    def log_tail(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return f.read()[-n:].decode("utf-8", "replace")
        except OSError:
            return ""

    def stop(self) -> int | None:
        """SIGTERM and wait: the server must drain and exit 0, or the
        chip is not released for the next process."""
        if self.proc is None or self.proc.poll() is not None:
            return self.proc.returncode if self.proc else None
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            return None

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


# ------------------------------------------------------------------ phases
def phase_write(cl: Client, ck: Checks, pushed: list[dict], sc: dict,
                rnd: random.Random, flushes: int) -> dict:
    """Push -> read back live -> live search -> flush -> read back from
    the cut block. With flushes=2 (multi-chip) the first half is cut
    into a block of its own before the second is pushed: two small
    blocks share one search job, the shape the mesh search program
    (db/search.search_blocks_device) takes."""
    log(f"write path: pushing {len(pushed)} traces x {sc['push_spans']} spans")
    acked: list[dict] = []
    t0 = time.perf_counter()
    nbytes = 0
    early_flush_at = (len(pushed) // 2 // 40) * 40 if flushes > 1 else -1
    for i in range(0, len(pushed), 40):
        if i == early_flush_at:
            status, _ = cl.request("POST", "/flush", label="flush_first_half")
            ck.check("flush first half", status == 204, f"HTTP {status}")
        batch = pushed[i:i + 40]
        body = encode_push(batch)
        nbytes += len(body)
        status, _ = cl.request("POST", "/v1/traces", body,
                               "application/x-protobuf")
        if ck.check(f"push batch {i // 40}", status == 200, f"HTTP {status}"):
            acked += batch
    push_s = time.perf_counter() - t0
    cl.timings.append({"request": "push_all", "smoke_wall_s": round(push_s, 3),
                       "bytes": nbytes, "requests": (len(pushed) + 39) // 40})
    ck.check("all pushes acknowledged", len(acked) == len(pushed),
             f"{len(acked)}/{len(pushed)}")

    sample = rnd.sample(acked, min(50, len(acked)))

    def read_back(tag: str) -> None:
        for n, tr in enumerate(sample):
            status, doc = cl.get_json(
                f"/api/traces/{tr['id'].hex()}",
                label=f"{tag}_find" if n == 0 else "")
            if not ck.check(f"{tag} find {tr['id'].hex()[:8]}", status == 200,
                            f"HTTP {status} for an acknowledged trace"):
                continue
            want = {(s.hex(), nm, a, b) for s, nm, a, b in tr["spans"]}
            ck.check(f"{tag} find {tr['id'].hex()[:8]} spans",
                     spans_of_otlp_json(doc) == want, "span set differs")

    def tag_search(tag: str, buckets: list[str]) -> None:
        now = int(time.time())
        for b in buckets:
            got = search_ids(cl, {"tags": f"smoke.bucket={b}", "limit": 200,
                                  "start": now - 3600, "end": now + 60},
                             f"{tag}_search")
            if got is not None:
                ck.equal_sets(f"{tag} search smoke.bucket={b}", got,
                              {t["id"].hex() for t in acked
                               if t["bucket"] == b})

    buckets = [f"b-{v:04d}"
               for v in rnd.sample(range(sc["push_buckets"]), 6)]
    read_back("live")
    tag_search("live", buckets[:3])
    status, _ = cl.request("POST", "/flush", label="flush")
    ck.check("flush", status == 204, f"HTTP {status}")
    read_back("cut")
    tag_search("cut", buckets[3:])
    return {"pushed_traces": len(pushed), "acked_traces": len(acked),
            "pushed_bytes": nbytes}


def phase_generator(cl: Client, ck: Checks, pushed: list[dict]) -> None:
    """The metrics-generator tap folds every push window (ops/reduce):
    span-metrics calls per service must add up to the spans pushed."""
    want: dict[str, int] = {}
    for tr in pushed:
        want[tr["service"]] = want.get(tr["service"], 0) + len(tr["spans"])
    got: dict[str, int] = {}
    deadline = time.monotonic() + 60
    while True:
        _, text = cl.request("GET", "/metrics")
        got = {}
        for line in text.decode().splitlines():
            if line.startswith("traces_spanmetrics_calls_total{"):
                svc = line.split('service="', 1)[1].split('"', 1)[0]
                if svc in want:
                    got[svc] = got.get(svc, 0) + int(float(line.rsplit(" ", 1)[1]))
        if got == want or time.monotonic() > deadline:
            break
        time.sleep(0.5)  # the tap is asynchronous
    ck.check("span-metrics calls per service", got == want,
             f"got {got} want {want}")


def phase_block_queries(cl: Client, ck: Checks, oracles: list[BlockOracle],
                        rnd: random.Random, rounds: int, tag: str) -> None:
    """Find, tag search, TraceQL attribute / duration / structural search
    and un-grouped rate() over the full-width block(s); each shape
    `rounds` times with a different operand value, so neither the result
    cache nor a kill switch answers the repeats."""
    window = search_window(oracles)
    start, end = window["start"], window["end"]

    def union(fn) -> set[str]:
        out: set[str] = set()
        for o in oracles:
            out |= fn(o)
        return out

    # trace by id: hits spread over every block, and misses
    n_hits = 20 if rounds > 1 else 4
    for n in range(n_hits):
        o = oracles[n % len(oracles)]
        sid = rnd.randrange(o.n_traces)
        hex_id = o.ids[sid].tobytes().hex()
        status, doc = cl.get_json(f"/api/traces/{hex_id}",
                                  label=f"{tag}_find" if n < 2 else "")
        if ck.check(f"{tag} find hit {hex_id[:8]}", status == 200,
                    f"HTTP {status}"):
            ck.check(f"{tag} find hit {hex_id[:8]} spans",
                     spans_of_otlp_json(doc) == o.trace_spans(sid),
                     "span set differs")
    for n in range(5 if rounds > 1 else 1):
        hex_id = rnd.getrandbits(128).to_bytes(16, "big").hex()
        status, doc = cl.get_json(f"/api/traces/{hex_id}",
                                  label=f"{tag}_find_miss" if n == 0 else "")
        ck.check(f"{tag} find miss {hex_id}", status == 404,
                 f"HTTP {status} for an id no block holds: "
                 f"{sorted(spans_of_otlp_json(doc or {}))[:1]}")

    for _ in range(rounds):
        svc = f"svc-{rnd.randrange(64):03d}"
        want = union(lambda o: o.traces_service(svc))
        got = search_ids(cl, {"tags": f"service.name={svc}",
                              "limit": len(want) + 100, **window},
                         f"{tag}_tag_search")
        if got is not None:
            ck.check(f"{tag} tag search {svc} non-empty", len(want) > 0)
            ck.equal_sets(f"{tag} tag search service.name={svc}", got, want)

    for _ in range(rounds):
        key = f"attr.key{rnd.randrange(1, 100):03d}"
        val = f"value-{rnd.randrange(5000):05d}"
        want = union(lambda o: o.traces_attr(key, val))
        got = search_ids(cl, {"q": f'{{ span.{key} = "{val}" }}',
                              "limit": len(want) + 100, **window},
                         f"{tag}_traceql_attr")
        if got is not None:
            ck.equal_sets(f'{tag} {{ span.{key} = "{val}" }}', got, want)

    for _ in range(rounds):
        ms = rnd.randrange(900, 990)
        want = union(lambda o: o.traces_duration_gt(ms * 1000))
        got = search_ids(cl, {"q": f"{{ duration > {ms}ms }}", "limit": 20,
                              **window}, f"{tag}_traceql_duration")
        if got is not None:
            ck.check(f"{tag} {{ duration > {ms}ms }} count",
                     len(set(got)) == min(20, len(want)),
                     f"got {len(set(got))} of limit 20, {len(want)} match")
            ck.check(f"{tag} {{ duration > {ms}ms }} members",
                     set(got) <= want, f"{len(set(got) - want)} do not match")

    for _ in range(rounds):
        key = f"attr.key{rnd.randrange(1, 100):03d}"
        val = f"value-{rnd.randrange(5000):05d}"
        ms = rnd.randrange(300, 700)
        want = union(lambda o: o.traces_descendant(key, val, ms * 1000))
        query = f'{{ span.{key} = "{val}" }} >> {{ duration > {ms}ms }}'
        got = search_ids(cl, {"q": query, "limit": len(want) + 100, **window},
                         f"{tag}_traceql_struct")
        if got is not None:
            ck.equal_sets(f"{tag} {query}", got, want)

    step_s = 60
    for _ in range(rounds):
        svc = f"svc-{rnd.randrange(64):03d}"
        query = f'{{ resource.service.name = "{svc}" }} | rate()'
        status, out = cl.get_json(
            "/api/metrics/query_range?" + q({"q": query, "start": start,
                                             "end": end, "step": step_s}),
            label=f"{tag}_metrics_rate")
        if not ck.check(f"{tag} {query} status", status == 200,
                        f"HTTP {status}"):
            continue
        step_ms = step_s * 1000
        start_ms = (start * 1000 // step_ms) * step_ms
        nb = -(-(end * 1000 - start_ms) // step_ms)
        want = sum(o.rate_counts(svc, start_ms, step_ms, nb) for o in oracles)
        series = out["data"]["result"]
        if not ck.check(f"{tag} {query} one series", len(series) == 1,
                        f"{len(series)} series"):
            continue
        got = [0] * nb
        for ts, v in series[0]["values"]:
            got[int(round((float(ts) * 1000 - start_ms) / step_ms))] = int(
                round(float(v) * step_s))
        ck.check(f"{tag} {query} non-empty", int(want.sum()) > 0)
        ck.check(f"{tag} {query} buckets", got == [int(x) for x in want],
                 f"total got {sum(got)} want {int(want.sum())}")


def phase_burst(port: int, ck: Checks, oracles: list[BlockOracle],
                rnd: random.Random, timings: list) -> None:
    """8 concurrent same-shape searches: the default batching executor
    coalesces window-mates into a fused multiquery + mq_select launch."""
    window = search_window(oracles)
    values = rnd.sample(range(700, 900), 8)
    results: dict[int, list | None] = {}
    together = threading.Barrier(len(values))

    def one(ms: int) -> None:
        cl = Client(port, ck, timings)
        cl.conn.connect()
        together.wait(timeout=60)  # all eight leave at once
        results[ms] = search_ids(
            cl, {"q": f"{{ duration > {ms}ms }}", "limit": 20, **window},
            "burst_traceql_duration")
        cl.conn.close()

    threads = [threading.Thread(target=one, args=(ms,)) for ms in values]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
        ck.check("burst request returned", not t.is_alive())
    for ms in values:
        got = results.get(ms)
        if got is None:
            continue
        want: set[str] = set()
        for o in oracles:
            want |= o.traces_duration_gt(ms * 1000)
        ck.check(f"burst {{ duration > {ms}ms }} count",
                 len(set(got)) == min(20, len(want)), f"got {len(set(got))}")
        ck.check(f"burst {{ duration > {ms}ms }} members", set(got) <= want,
                 f"{len(set(got) - want)} do not match")


def launches_by_op(kernels: list[dict]) -> dict[str, dict]:
    out: dict[str, dict] = {}
    for k in kernels:
        row = out.setdefault(k["op"], {"launches": 0, "compiles": 0,
                                       "buckets": []})
        row["launches"] += k["compiles"] + k["cache_hits"]
        row["compiles"] += k["compiles"]
        row["buckets"].append(k["bucket"])
    return out


def phase_status(cl: Client, ck: Checks, on_chip: bool, chips: int,
                 expect_cache_dir: str) -> dict:
    _, kern = cl.get_json("/status/kernels", label="status_kernels")
    _, cost = cl.get_json("/status/cost", label="status_cost")
    status, _ = cl.request("GET", "/metrics", label="metrics")
    ck.check("/metrics", status == 200, f"HTTP {status}")
    ops = launches_by_op(kern["kernels"])
    routing = kern["routing"]
    notes = []
    if on_chip:
        # a CPU dry run routes block cut and small scans to the host by
        # design; which kernels launched is only a verdict on the chip
        for op in REQUIRED_OPS + (REQUIRED_OPS_ONE_CHIP if chips == 1
                                  else REQUIRED_OPS_MESH):
            launched = ops.get(op, {}).get("launches", 0) > 0
            if not launched and any(
                    r["layer"] == ROUTED_BY_LINK_RTT.get(op)
                    and (r["engine"], r["reason"]) == ("host", "link_rtt")
                    for r in routing):
                notes.append(f"{op}: routed to the host, link RTT "
                             f"{kern['device']['link_rtt_ms']:.3f} ms "
                             "measured against the 2 ms constant")
                continue
            ck.check(f"kernel {op} launched", launched, "zero launches")
    for r in routing:
        ck.check(f"routing {r['layer']}/{r['engine']}/{r['reason']}",
                 r["reason"] not in BAD_ROUTING_REASONS,
                 f"{r['count']} decisions")
    mesh_ops = sorted(op for op in ops if op.startswith("mesh_"))
    cache = cost["compile_cache"]
    ck.check("compile cache directory", cache["dir"] == expect_cache_dir,
             f"{cache['dir']!r} != {expect_cache_dir!r}")
    ck.check("native codec library", kern["native"]["available"],
             "server reports the pure-Python fallbacks")
    ck.check("cost capture errors", cost["capture"]["capture_errors"] == 0,
             str([p["error"] for p in cost["programs"] if p.get("error")][:3]))
    hbm = cost["hbm"]
    return {
        "ops": ops, "mesh_ops": mesh_ops, "routing": routing, "notes": notes,
        "compile_cache": cache,
        "compile_seconds_total": round(
            sum(cache["compile_seconds_by_op"].values()), 3),
        "memory_stats": hbm.get("per_device_memory_stats"),
        "hbm_accounted": {k: v.get("bytes") for k, v in
                          hbm["components"].items()},
        "staged_cache": {k: kern["staged_cache"].get(k)
                         for k in ("bytes", "entries", "budget_bytes")},
        "link_rtt_ms": kern["device"].get("link_rtt_ms"),
        "native": kern["native"],
    }


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--scale", choices=sorted(SCALES), default="full",
                    help="tiny exists only for the CPU dry run")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="debugging only: accept a server on the CPU "
                         "(needs JAX_PLATFORMS=cpu); never a chip result")
    ap.add_argument("--keep", action="store_true",
                    help="keep the storage directory")
    ap.add_argument("--deadline", type=float, default=1150.0,
                    help="give up (exit 3, server killed) after this many "
                         "seconds: a hung run must not outlive its slot")
    args = ap.parse_args(argv)
    sc = SCALES[args.scale]
    t_start = time.perf_counter()

    # 1. the codec library, from the committed source and nothing else
    try:
        mk = subprocess.run(["make", "-B", "-C", os.path.join(REPO, "native")],
                            capture_output=True, text=True, timeout=600)
    except (OSError, subprocess.SubprocessError) as e:
        print(f"chip_smoke: cannot build native/: {e}", file=sys.stderr)
        return 2
    if mk.returncode != 0:
        print("chip_smoke: `make -B -C native` failed:\n"
              + mk.stdout[-2000:] + mk.stderr[-2000:], file=sys.stderr)
        return 2
    try:
        sys.path.insert(0, REPO)
        from tempo_tpu.backend.local import LocalBackend
        from tempo_tpu.util.testdata import synth_columns, write_synth_block
    except ImportError as e:
        print(f"chip_smoke: not inside a tempo-tpu checkout: {e}",
              file=sys.stderr)
        return 2

    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, "chip_smoke_server.log")
    open(log_path, "wb").close()
    storage = tempfile.mkdtemp(prefix="chip-smoke-")
    ck = Checks()
    timings: list[dict] = []
    server = Server(storage, log_path)
    summary: dict = {}

    def give_up() -> None:
        print(f"chip_smoke: no result after {args.deadline:.0f} s; last "
              f"request timings: {timings[-3:]}", file=sys.stderr, flush=True)
        server.kill()
        os._exit(3)

    watchdog = threading.Timer(args.deadline, give_up)
    watchdog.daemon = True
    watchdog.start()
    try:
        # 2. corpus, dated now. Block b's spans fill the last full hour
        # before the top of the current one (minus b hours); the pushed
        # traces are seconds old. No two blocks of the store then share
        # a one-hour compaction window (db/compactor.select_jobs), so no
        # compaction runs under the requests: while a compaction's
        # inputs stay searchable beside its output
        # (blocklist.COMPACTED_GRACE_S) metrics count their spans twice
        # -- a finding for a cell with background work (ROADMAP A12), not
        # something a start-up smoke should trip over at random.
        hour_ns = 3600 * 1_000_000_000
        if time.time_ns() % hour_ns < 40 * 1_000_000_000:
            time.sleep(40)  # keep the pushed traces inside this hour
        t0 = time.perf_counter()
        now_ns = time.time_ns()
        top_ns = now_ns - now_ns % hour_ns
        oracles = []
        backend = LocalBackend(storage)
        for b in range(args.chips):
            rng = np.random.default_rng([args.seed, b])
            cols, strings, ids = synth_columns(
                rng, sc["traces"], sc["spans_per"],
                base_time_ns=top_ns - (b + 1) * hour_ns - 2_000_000_000)
            meta = write_synth_block(backend, TENANT, cols, strings, ids)
            oracles.append(BlockOracle(cols, strings, ids, sc["spans_per"]))
            del cols
            log(f"block {meta.block_id[:8]}: {meta.total_traces} traces, "
                f"{meta.total_spans} spans, {meta.size_bytes >> 20} MiB")
        pushed = make_push_corpus(args.seed, sc, now_ns)
        corpus_s = time.perf_counter() - t0
        total_spans = sum(o.c["span.trace_sid"].shape[0] for o in oracles)

        # 3. the server, through its normal entry point
        start_s = server.start()
        cl = Client(server.port, ck, timings)
        _, kern = cl.get_json("/status/kernels")
        dev = kern["device"]
        log(f"server ready in {start_s:.1f}s on {dev}")
        on_chip = dev["platform"] == "tpu"
        if on_chip and dev["peaks"] == "unknown":
            raise SmokeFailure(
                f"device_kind {dev['device_kind']!r} is not in the peaks "
                "table (tempo_tpu/util/costmodel.DEVICE_PEAKS)")
        if not on_chip:
            if not (args.allow_cpu and dev["platform"] == "cpu"):
                raise SmokeFailure(
                    f"the server runs on platform {dev['platform']!r}, not "
                    "a TPU: this is not a chip result (--allow-cpu for a "
                    "CPU dry run)")
            print("CPU DRY RUN -- not a chip result", flush=True)
        if dev["count"] != args.chips and on_chip:
            raise SmokeFailure(
                f"--chips {args.chips} but jax reports {dev['count']} devices")
        expect_cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            os.path.join(REPO, ".jax_cache")

        # 4. requests, each answer against the oracle
        rnd = random.Random(f"{args.seed}-requests")
        write = phase_write(cl, ck, pushed, sc, rnd,
                            flushes=2 if args.chips > 1 else 1)
        phase_generator(cl, ck, pushed)
        t_first = time.perf_counter()
        phase_block_queries(cl, ck, oracles, rnd, rounds=3, tag="block")
        cold_first = next(t["smoke_wall_s"] for t in timings
                          if t["request"] == "block_traceql_attr")
        log(f"block queries done in {time.perf_counter() - t_first:.1f}s")
        # whether eight requests meet inside the executor's 3 ms window
        # is up to the host's scheduling: repeat the burst (new operands)
        # until a fused launch is seen. On one chip it is required; on
        # several the window-mates rarely meet (host work per query
        # serialises them), so the batched mesh window is reported only
        bursts = 0
        fused = False
        while not fused and bursts < MAX_BURSTS:
            phase_burst(server.port, ck, oracles, rnd, timings)
            bursts += 1
            # the burst's requests return as soon as other shards fill
            # their limit, possibly while the fused launch still compiles
            # (61 s cold): wait for its select half before judging
            wait_until = time.monotonic() + 180
            while True:
                _, kern = cl.get_json("/status/kernels")
                launched = {k["op"] for k in kern["kernels"]}
                fused = any(op in launched for op in FUSED_OPS)
                if (not fused or "mq_select" in launched
                        or time.monotonic() > wait_until):
                    break
                time.sleep(2)

        # 5. what the server says about itself
        first = phase_status(cl, ck, on_chip, args.chips, expect_cache)

        # one last acknowledged write that no flush follows: after the
        # restart it can only come back from the WAL
        late = make_push_corpus(args.seed, sc, time.time_ns(), n_traces=40)
        status, _ = cl.request("POST", "/v1/traces", encode_push(late),
                               "application/x-protobuf", label="push_late")
        ck.check("late push acknowledged", status == 200, f"HTTP {status}")
        cl.conn.close()
        rc = server.stop()
        ck.check("server exits 0 on SIGTERM", rc == 0, f"exit code {rc}")

        # 6. restart on the same storage and cache directories
        restart_s = server.start()
        cl = Client(server.port, ck, timings)
        n_before = len(timings)
        phase_block_queries(cl, ck, oracles, rnd, rounds=1, tag="restart")
        warm_first = next(t["smoke_wall_s"] for t in timings[n_before:]
                          if t["request"] == "restart_traceql_attr")
        # acknowledged writes survive the restart: the flushed ones in
        # their blocks, the late ones through WAL replay
        for tr in rnd.sample(pushed, min(10, len(pushed))) + late:
            status, doc = cl.get_json(f"/api/traces/{tr['id'].hex()}")
            ck.check(f"restart find pushed {tr['id'].hex()[:8]}",
                     status == 200 and spans_of_otlp_json(doc) == {
                         (s.hex(), nm, a, b) for s, nm, a, b in tr["spans"]},
                     f"HTTP {status}")
        _, cost2 = cl.get_json("/status/cost", label="restart_status_cost")
        cache2 = cost2["compile_cache"]
        ck.check("restarted server reads the persistent compile cache",
                 cache2["disk_hits"] > 0, f"{cache2}")
        cl.conn.close()
        rc = server.stop()
        ck.check("restarted server exits 0 on SIGTERM", rc == 0,
                 f"exit code {rc}")

        dryrun = None
        if args.chips > 1 and on_chip:
            # the chips are free again: certify the collectives over ICI
            # (toy, then >= 1 M padded rows per chip) against the oracle
            log(f"__graft_entry__.py --dryrun {args.chips}")
            p = subprocess.run(
                [sys.executable, os.path.join(REPO, "__graft_entry__.py"),
                 "--dryrun", str(args.chips)],
                cwd=REPO, capture_output=True, text=True, timeout=900)
            ck.check("__graft_entry__ --dryrun", p.returncode == 0,
                     p.stderr[-1500:])
            dryrun = [ln for ln in p.stdout.splitlines()
                      if ln.startswith(("MULTICHIP_SCALE", "dryrun_"))]

        summary = {
            "ok": not ck.failures,
            "cpu_dry_run": not on_chip,
            "device": {"platform": dev["platform"],
                       "kind": dev["device_kind"], "count": dev["count"]},
            "device_peaks": dev["peaks"],
            "scale": args.scale, "seed": args.seed,
            "blocks": len(oracles), "block_spans": total_spans,
            "block_traces": sum(o.n_traces for o in oracles),
            **write,
            "checks_passed": ck.passed, "failures": ck.failures,
            "corpus_s": round(corpus_s, 2),
            "server_start_s": round(start_s, 2),
            "server_restart_s": round(restart_s, 2),
            "first_traceql_search_cold_s": cold_first,
            "first_traceql_search_restarted_s": warm_first,
            "bursts": bursts, "burst_fused_launch": fused,
            "first_server": first,
            "restarted_compile_cache": cache2,
            "dryrun_multichip": dryrun,
            "smoke_timings": timings,
            "total_s": round(time.perf_counter() - t_start, 1),
            "claim": None,
        }
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    finally:
        watchdog.cancel()
        server.kill()
        if not args.keep:
            shutil.rmtree(storage, ignore_errors=True)
    # this process never touched jax, so it never held the chip
    if "jax" in sys.modules:
        print("chip_smoke: the parent process imported jax", file=sys.stderr)
        return 1
    with open(os.path.join(out_dir, "chip_smoke_summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    if ck.failures:
        print(f"chip_smoke: {len(ck.failures)} check(s) failed; first: "
              f"{ck.failures[0]}", file=sys.stderr)
    # the verdict line: these keys and no others (the summary above and
    # chiprun_out/chip_smoke_summary.json carry everything else)
    print(json.dumps({"ok": summary["ok"], "device": summary["device"]}),
          flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
