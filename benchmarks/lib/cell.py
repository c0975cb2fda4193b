"""One pass of one cell: storage, server, warm-up, window, checks, metrics."""

from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import threading
import time
import zipfile

from . import corpus as corpus_lib
from . import harness as H
from . import readers as R
from . import selftrace, stats
from .server import Client, Server, ServerFailure

ROOT = corpus_lib.ROOT
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmarks/


TRACE_ATTEMPTS = 3  # device-trace sessions a traced run may take


class NoChip(Exception):
    """The server does not run on the device the cell asks for."""


def xspace_has_device(trace_zip: bytes) -> bool:
    """Whether the profiler's file has a TPU plane: it writes one only when
    something ran there. Plane names are plain strings in the protobuf, so
    this needs no jax."""
    with zipfile.ZipFile(io.BytesIO(trace_zip)) as z:
        return any(b"/device:TPU:" in z.read(n) for n in z.namelist()
                   if n.endswith(".xplane.pb"))


def out_dir() -> str:
    d = os.path.join(ROOT, "chiprun_out")
    os.makedirs(d, exist_ok=True)
    return d


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """-> (BENCHMARK.json, its workloads entry, configuration, mix)."""
    bench = H.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = H.load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = H.load_json(os.path.join(BENCH, "mixes", cell["traffic"] + ".json"))
    return bench, cell, config, mix


def ensure_native() -> None:
    """`make -C native` only when the library is older than its source."""
    so = os.path.join(ROOT, "native", "libvtpu_native.so")
    src = os.path.join(ROOT, "native", "vtpu_native.cc")
    if os.path.exists(so) and os.path.getmtime(so) >= os.path.getmtime(src):
        return
    mk = subprocess.run(["make", "-B", "-C", os.path.join(ROOT, "native")],
                        capture_output=True, text=True, timeout=600)
    if mk.returncode != 0:
        raise RuntimeError("`make -C native` failed:\n" + mk.stdout[-2000:]
                           + mk.stderr[-2000:])


def compile_cache_dir() -> str:
    """Where the server keeps jax's persistent cache (util/costmodel): the
    environment's directory if it names one, else <checkout>/.jax_cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")


class CellPass:
    """Set-up, one window and the checks after it, against one server."""

    def __init__(self, cell: dict, config: dict, mix: dict, manifest: dict,
                 seed: int, seconds: float, trace: bool, allow_cpu: bool,
                 tag: str):
        self.cell, self.config, self.mix, self.manifest = cell, config, mix, manifest
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.allow_cpu = allow_cpu
        self.run_dir = corpus_lib.bench_dir("run", cell["name"])
        os.makedirs(self.run_dir, exist_ok=True)
        self.log_path = os.path.join(out_dir(), f"bench-{cell['name']}-{tag}-server.log")
        open(self.log_path, "wb").close()
        self.server: Server | None = None
        self.env = H.Env(config, mix, manifest, seed)
        self.streams = {s["name"]: H.StreamState(mix["name"], s, self.env)
                        for s in mix["streams"]}
        self.warm: list[dict] = []
        self.extras: dict = {}
        self.trace_span = None
        self.trace_zip = os.path.join(self.run_dir, "trace.zip")
        self.device: dict = {}

    # ------------------------------------------------------------- set-up
    def start(self) -> None:
        storage = os.path.join(self.run_dir, "storage")
        corpus_lib.link_store(self.manifest, storage)
        args = list(self.config.get("server_args", []))
        if self.trace:
            args += ["--self-tracing.tenant", "self"]
        env = None
        if self.allow_cpu:
            # rehearsal only: the CPU backend with as many virtual devices as
            # the cell has chips. A measured run passes the environment as is.
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            if self.config["chips"] > 1:
                env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                    " --xla_force_host_platform_device_count="
                                    f"{self.config['chips']}").strip()
        self.server = Server(storage, self.log_path, args, env)
        ready_s = self.server.start()
        self.port = self.server.port
        kern = self.kernels()
        dev = kern["device"]
        H.log(f"server ready in {ready_s:.1f}s on {dev}")
        self.device = dev
        peaks = H.load_json(os.path.join(BENCH, "lib", "peaks.json"))["peaks"]
        if dev["platform"] == "tpu":
            if dev["device_kind"] not in peaks:
                raise NoChip(f"device_kind {dev['device_kind']!r} is not in "
                             "benchmarks/lib/peaks.json")
            if dev["count"] != self.config["chips"]:
                raise NoChip(f"the cell asks for {self.config['chips']} chips, "
                             f"jax reports {dev['count']}")
        elif not (self.allow_cpu and dev["platform"] == "cpu"):
            raise NoChip(f"the server runs on platform {dev['platform']!r}, "
                         "not a TPU (--allow-cpu --scale tiny rehearses)")

    def kernels(self) -> dict:
        cl = Client(self.port, timeout=60)
        status, out = cl.get_json("/status/kernels")
        cl.close()
        if out is None:
            raise ServerFailure(f"/status/kernels answered HTTP {status}")
        return out

    def cost(self) -> dict:
        cl = Client(self.port, timeout=60)
        _, out = cl.get_json("/status/cost")
        cl.close()
        return out or {}

    def blocks_compacted(self) -> int | None:
        """Input blocks the served compactor has merged away so far, by
        either of its drivers (`/metrics` `tempo_compactor_blocks_compacted_total`:
        `/status/kernels` `compaction.jobs` counts the pipeline's jobs only,
        and the default driver is not the pipeline). None where the process
        runs no compactor."""
        cl = Client(self.port, timeout=60)
        status, text = cl.request("GET", "/metrics")
        cl.close()
        if status != 200:
            raise ServerFailure(f"/metrics answered HTTP {status}")
        return R.metrics_family_total(text.decode("utf-8", "replace"),
                                      "tempo_compactor_blocks_compacted_total")

    def warm_up(self) -> None:
        self.warm = H.warm_up(self.streams, self.mix, self.env, self.port,
                              self.kernels)
        for st in self.streams.values():
            st.window_from = st.cursor

    # ------------------------------------------------------------- window
    def _capture_trace(self, t0: float, seconds: float) -> None:
        """Ask the server for a device trace of its own process and fetch
        the artifact: in the middle of the window, or from the share of it
        the mix names. A session in which nothing ran on the TPU (the file
        then has no device plane), or that failed, is taken again, at most
        twice: the Python tracer that /debug/profile/device always runs slows a
        server that decodes pushes ~14x, and its launches with it."""
        span = min(float(self.mix.get("trace_seconds", 8)), seconds / 2)
        at = self.mix.get("trace_at_share")
        start = (seconds - span) / 2 if at is None else at * seconds
        wait = t0 + start - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        cl = Client(self.port, timeout=600)
        for _ in range(TRACE_ATTEMPTS):
            t_a = time.perf_counter()
            status, out = cl.get_json(f"/debug/profile/device?seconds={span}")
            data = None
            if out is not None:
                status, data = cl.request(
                    "GET", "/debug/profile/artifact/" + out["artifact_id"])
            on_device = False
            if data is None or status != 200:
                H.log(f"device trace: HTTP {status}")
                time.sleep(1.0)
            else:
                # (the rehearsal's CPU backend has no device plane to wait for)
                on_device = (self.device["platform"] != "tpu"
                             or xspace_has_device(data))
                H.log(f"device trace: {len(data)} bytes, {span:.0f}s from "
                      f"{t_a - t0:.1f}s"
                      + ("" if on_device else ": nothing ran on the TPU in it"))
                self.trace_span = (t_a, t_a + span)
                with open(self.trace_zip, "wb") as f:
                    f.write(data)
            if on_device or time.perf_counter() + span > t0 + seconds:
                break
        cl.close()

    def window(self, phase: str) -> None:
        if os.path.exists(self.trace_zip):
            os.remove(self.trace_zip)
        self.kernels_before = self.kernels()
        compacted_before = self.blocks_compacted()
        self.window_unix = time.time()
        extra = [self._capture_trace] if self.trace else []
        self.t0, self.t_end, self.events = H.run_window(
            self.streams, self.mix, self.port, self.seconds, phase, extra)
        self.kernels_after = self.kernels()
        compacted_after = self.blocks_compacted()
        self.blocks_compacted_in_window = (
            None if None in (compacted_before, compacted_after)
            else compacted_after - compacted_before)
        self.cost_after = self.cost()

    # -------------------------------------------------------------- after
    def after_window(self) -> list[dict]:
        out = []
        self.selftraces = None
        if self.trace:
            time.sleep(1.0)  # the self-trace shipper is asynchronous
            self.selftraces = selftrace.read_back(self.port, "self",
                                                  self.window_unix)
        for spec in self.mix.get("after_window", []):
            mod = H.load_plugin("checks", spec["check"])
            res, extras = mod.run(self, spec)
            out += res
            self.extras.update(extras)
        return out

    def stop(self) -> int | None:
        return self.server.stop() if self.server else None

    def kill(self) -> None:
        if self.server:
            self.server.kill()

    def reduce_trace(self) -> dict | None:
        """The .xplane.pb -> numbers, in a process of its own on the CPU
        backend (this one never imports jax)."""
        if not os.path.exists(self.trace_zip):
            return None
        dst = os.path.join(self.run_dir, "trace.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        asked_s = self.trace_span[1] - self.trace_span[0]
        p = subprocess.run([sys.executable, os.path.join(BENCH, "lib", "xplane.py"),
                            self.trace_zip, dst, str(asked_s)], env=env, cwd=ROOT,
                           capture_output=True, text=True, timeout=600)
        if p.returncode != 0:
            H.log("trace reduction failed:\n" + p.stderr[-2000:])
            return None
        return H.load_json(dst)

    # ------------------------------------------------------------ metrics
    def context(self, setup_s: float, trace: dict | None) -> dict:
        streams = {}
        for name, st in self.streams.items():
            streams[name] = {"spec": st.spec,
                             "results": [r for r in st.results
                                         if r["phase"] == "window"]}
        return {
            "seconds": self.seconds, "t0": self.t0, "t_end": self.t_end,
            "streams": streams, "config": self.config, "mix": self.mix,
            "manifest": self.manifest, "setup_s": setup_s,
            "kernels_before": self.kernels_before,
            "kernels_after": self.kernels_after, "cost_after": self.cost_after,
            "blocks_compacted_in_window": self.blocks_compacted_in_window,
            "selftrace": self.selftraces, "trace": trace,
            "trace_span": self.trace_span,
            "extras": self.extras,
            "device": self.device, "env": self.env,
            "module_ops": H.load_json(os.path.join(BENCH, "lib", "module_ops.json")),
        }


def read_metrics(bench: dict, cell: dict, group: str, ctx: dict) -> dict:
    """The cell's metrics of one group (`end_to_end` or `per_layer`), each
    by its reader, found by name. A reader that returns None is left out."""
    kind = "e2e_metrics" if group == "end_to_end" else "layer_metrics"
    reported_e2e = {m["name"] for m in bench["end_to_end"]
                    if cell["name"] in m.get("workloads", [cell["name"]])}
    out = {}
    for m in bench[group]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        if group == "per_layer" and m["moves"] not in reported_e2e:
            continue
        try:
            v = H.load_plugin(kind, m["name"]).read(ctx)
        except Exception as e:  # a reader that breaks loses its metric only
            H.log(f"metric {m['name']}: reader raised {type(e).__name__}: {e}")
            v = None
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(problems: list, attempted: int, failed: int, metrics: dict,
                device: dict, memory_peak_bytes: int, trace: dict | None,
                checks: dict | None = None) -> dict:
    """The run's last stdout line: the contract's keys and, last, `checks`:
    every number `correct` was decided from beside its limit."""
    dev = {"platform": device["platform"], "kind": device["device_kind"],
           "count": device["count"], "memory_peak_bytes": memory_peak_bytes}
    line = {"correct": not problems, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": dev}
    if trace and trace.get("devices"):
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    line["checks"] = checks or {}
    return line


def summarize(ctx: dict) -> dict:
    """Counts and medians per stream and shape, for the lines before the
    last (never a metric; a reader of the log sees what the window held)."""
    out = {}
    for name, st in ctx["streams"].items():
        shapes: dict = {}
        for r in st["results"]:
            s = shapes.setdefault(r["op"]["shape"], [])
            s.append(r)
        out[name] = {sh: _latencies(rs) for sh, rs in shapes.items()}
    return out


def _latencies(rs: list[dict]) -> dict:
    """One shape's answers of a window, from when each was due: enough
    percentiles (and the mean) to weigh a statistic's spread between runs
    without another run."""
    ms = [(r["t_done"] - r["t_due"]) * 1e3 for r in rs]
    # an open loop's generator stamps when it let a request go: how late, at
    # the worst, tells a stall of the generator (or of the host under it)
    # from one of the server, which `max_ms` (from the send) bounds
    late = [(r["t_disp"] - r["t_due"]) * 1e3 for r in rs if "t_disp" in r]
    return {"n": len(rs), "failed": sum(not R.good(r) for r in rs),
            **{f"p{int(q * 100)}_ms": stats.percentile(ms, q)
               for q in (0.5, 0.75, 0.9, 0.95, 0.99)},
            "mean_ms": sum(ms) / len(ms),
            "max_ms": max((r["t_done"] - r["t_send"]) * 1e3 for r in rs),
            "late_max_ms": max(late, default=None)}


def write_json(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, default=str)


def start_watchdog(deadline_s: float, on_fire) -> threading.Timer:
    t = threading.Timer(deadline_s, on_fire)
    t.daemon = True
    t.start()
    return t
