"""Device cost observability: program cost analysis, collective comm
accounting, and the HBM ledger.

kerneltel (PR 2) says how long each kernel RAN; this module says what
each kernel COSTS and whether the time was well spent:

  * **Program cost analysis** -- on every new compile (the
    TEL.record_launch chokepoint passes a capture thunk), a background
    worker lowers the same program against abstract avals and records
    XLA's own `cost_analysis()` (FLOPs, bytes accessed) and
    `memory_analysis()` (argument/output/temp/code bytes) per
    (op, shape-bucket). Paired with kerneltel's measured wall-time
    histograms this yields achieved-vs-roofline utilization per kernel
    in /status/cost. Capture happens OFF the query path: the hot path
    only enqueues ShapeDtypeStructs (never live device arrays).

  * **Collective comm accounting** -- for mesh programs the capture
    also traces a jaxpr and statically walks it for collectives
    (all_gather / psum / pmax / pmin / psum_scatter / reduce_scatter /
    all_to_all / ppermute), pricing each with the standard ring-
    algorithm model (see collective_comm_bytes) times the number of
    independent device groups. Per-launch bytes x launch counts feed
    `tempo_mesh_comm_bytes_total{collective,op}` -- ROADMAP item 2(a)'s
    "how big IS the struct-op all_gather" made a first-class series.

  * **HBM ledger** -- one device-memory view unifying the staged
    block-column cache (ops/stage), live-head staging tails
    (ops/livestage) and the compiled-program footprint, cross-checked
    against device.memory_stats() where the backend provides it, with
    watermark gauges feeding the TempoHBMPressure alert.

  * **Persistent compilation cache** -- always on, so restarts stop
    paying the first-compile storm: in the directory
    JAX_COMPILATION_CACHE_DIR names when it is set (jax reads it; this
    module never overrides it), else --compile-cache.dir, else the fixed
    `<checkout>/.jax_cache`. `tempo_kernel_compile_disk_total{outcome}`
    (fed by jax.monitoring events) splits disk-cache hits from fresh
    XLA compiles, the complement of kerneltel's in-process
    jit-cache-hit counter.

  * **Device identity** -- resolve_device() names the backend this
    process got (platform, device_kind, count) once at start-up and
    refuses the CPU unless JAX_PLATFORMS asked for it; DEVICE_PEAKS is
    the one peaks table, keyed by device_kind.

Kill switches: TEMPO_COSTMODEL=0 disables capture entirely (launch
counting stays, it is two dict increments); TEMPO_COSTMODEL_MEMORY=0
skips the background `compile()` that memory_analysis needs, keeping
capture to trace+lower. Everything here is advisory: no method may
raise into the query path.
"""

from __future__ import annotations

import os
import threading
import time

# Published per-chip peaks, keyed by the `device_kind` jax reports.
# Source: Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s bf16,
# 16 GB of HBM at 819 GB/s. A kind missing here has no roofline:
# /status/cost says "unknown".
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}
HBM_PEAK_BPS = {kind: p["hbm_bytes_per_s"] for kind, p in DEVICE_PEAKS.items()}

# collectives the comm walker prices (jaxpr primitive names)
_COLLECTIVES = ("all_gather", "psum", "pmax", "pmin", "psum_scatter",
                "reduce_scatter", "all_to_all", "ppermute")


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * int(aval.dtype.itemsize)
    except Exception:
        return 0


def _axis_group_size(params, mesh_axis_sizes: dict[str, int]) -> int:
    """Number of devices participating in one collective group: the
    product of the collective's named axes' sizes."""
    axes = params.get("axis_name", params.get("axes", ()))
    if isinstance(axes, str):
        axes = (axes,)
    k = 1
    for a in axes or ():
        k *= int(mesh_axis_sizes.get(a, 1))
    return max(k, 1)


def _sub_jaxprs(params):
    for v in params.values():
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for b in v:
                if hasattr(b, "eqns"):
                    yield b
                elif hasattr(b, "jaxpr") and hasattr(b.jaxpr, "eqns"):
                    yield b.jaxpr


def ring_wire_bytes(name: str, in_bytes: int, out_bytes: int, k: int) -> int:
    """Wire bytes one collective moves for ONE group of k devices under
    the standard ring algorithms (the walker's pricing model, exported
    so tests and the mesh-batch bench can hand-compute the expected
    totals and cross-check the jaxpr walk):
      all_gather      out_bytes x (k-1)   (each of k receives the
                                           (k-1)/k it lacks)
      psum/pmax/pmin  2 x in_bytes x (k-1)  (ring all-reduce)
      ppermute        in_bytes x k          (every shard moves)
      psum_scatter / reduce_scatter /
      all_to_all      in_bytes x (k-1)"""
    if name == "all_gather":
        return out_bytes * (k - 1)
    if name in ("psum", "pmax", "pmin"):
        return 2 * in_bytes * (k - 1)
    if name == "ppermute":
        return in_bytes * k
    return in_bytes * (k - 1)  # psum_scatter / reduce_scatter / all_to_all


def collective_comm_bytes(jaxpr, mesh_axis_sizes: dict[str, int],
                          total_devices: int) -> dict[str, int]:
    """Statically price every collective in a jaxpr: fleet-wide wire
    bytes per program execution, by collective name.

    Model: ring_wire_bytes (k = devices in one collective group) times
    g = total_devices / k independent groups running the collective.
    Shapes inside shard_map are PER-SHARD; in/out bytes are the
    eqn's own aval bytes, so the model needs no sharding inference.
    Recursion: sub-jaxprs (pjit/shard_map/custom calls) count once,
    `scan` bodies multiply by the trip count, `cond` branches take the
    max (conservative for routing, never an undercount of the worst
    branch)."""
    out: dict[str, int] = {}

    def add(dst: dict[str, int], src: dict[str, int], mul: int = 1) -> None:
        for kk, vv in src.items():
            dst[kk] = dst.get(kk, 0) + vv * mul

    def walk(jx) -> dict[str, int]:
        acc: dict[str, int] = {}
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in _COLLECTIVES:
                k = _axis_group_size(eqn.params, mesh_axis_sizes)
                groups = max(1, total_devices // k)
                in_b = sum(_aval_bytes(v.aval) for v in eqn.invars
                           if hasattr(v, "aval"))
                out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
                wire = ring_wire_bytes(name, in_b, out_b, k)
                acc[name] = acc.get(name, 0) + wire * groups
            if name == "cond":
                branches = [walk(b.jaxpr if hasattr(b, "jaxpr") else b)
                            for b in eqn.params.get("branches", ())]
                if branches:
                    worst: dict[str, int] = {}
                    for b in branches:
                        for kk in set(worst) | set(b):
                            worst[kk] = max(worst.get(kk, 0), b.get(kk, 0))
                    add(acc, worst)
                continue
            mul = int(eqn.params.get("length", 1)) if name == "scan" else 1
            for sub in _sub_jaxprs(eqn.params):
                add(acc, walk(sub), mul)
        return acc

    add(out, walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr))
    return out


# --------------------------------------------------------- program specs


class ProgramSpec:
    """Everything the background worker needs to re-derive one compiled
    program's costs: the jitted callable plus ABSTRACT argument avals
    (built eagerly at the call site, so the spec never pins live device
    arrays), and the mesh shape for comm pricing (None = single-device
    program, no jaxpr walk)."""

    __slots__ = ("fn", "args", "kwargs", "mesh_axis_sizes", "mesh_devices")

    def __init__(self, fn, args, kwargs, mesh_axis_sizes, mesh_devices):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.mesh_axis_sizes = mesh_axis_sizes
        self.mesh_devices = mesh_devices


def spec(fn, *args, mesh=None, **kwargs) -> ProgramSpec:
    """Build a capture spec at a launch site. Array-likes (anything with
    a dtype) become ShapeDtypeStructs; python ints/bools/strings pass
    through untouched so static args still key the lowering."""
    import jax

    def absify(x):
        if hasattr(x, "dtype") and hasattr(x, "shape"):
            import numpy as np

            return jax.ShapeDtypeStruct(tuple(x.shape), np.dtype(x.dtype))
        return x

    a_args = jax.tree_util.tree_map(absify, args)
    a_kwargs = jax.tree_util.tree_map(absify, kwargs)
    axis_sizes = dict(mesh.shape) if mesh is not None else None
    n_dev = int(mesh.devices.size) if mesh is not None else 1
    return ProgramSpec(fn, a_args, a_kwargs, axis_sizes, n_dev)


# ------------------------------------------------------------ cost model


class CostModel:
    """Process-wide capture store + background analysis worker."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: list[tuple[str, str, ProgramSpec]] = []
        self._pending = 0
        self._worker: threading.Thread | None = None
        # (op, bucket) -> analysis row (last capture wins; one row per
        # shape bucket is the granularity the kernel table also uses)
        self._programs: dict[tuple[str, str], dict] = {}
        self._launches: dict[tuple[str, str], int] = {}
        self._captures = 0
        self._capture_errors = 0
        self._hbm_peak = 0

    # ------------------------------------------------------------ config
    @staticmethod
    def enabled() -> bool:
        return os.environ.get("TEMPO_COSTMODEL", "1") != "0"

    @staticmethod
    def _memory_enabled() -> bool:
        return os.environ.get("TEMPO_COSTMODEL_MEMORY", "1") != "0"

    # ----------------------------------------------------------- capture
    def note_launch(self, op: str, bucket_label: str) -> None:
        """Every kernel launch (compile or cache hit) lands here from
        record_launch: launch counts turn per-program comm bytes into
        the tempo_mesh_comm_bytes_total counter."""
        with self._lock:
            key = (op, bucket_label)
            self._launches[key] = self._launches.get(key, 0) + 1

    def enqueue(self, op: str, bucket_label: str, program: ProgramSpec) -> None:
        """Queue one new program for background analysis."""
        if not self.enabled():
            return
        with self._cv:
            self._queue.append((op, bucket_label, program))
            self._pending += 1
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._run, daemon=True, name="costmodel")
                self._worker.start()
            self._cv.notify_all()

    def drain(self, timeout: float = 5.0) -> bool:
        """Wait for queued captures to finish (tests, /status/cost)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._pending > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
        return True

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._queue:
                    self._cv.wait()
                op, blab, program = self._queue.pop(0)
            try:
                entry = self._analyze(program)
            except Exception as e:  # the worker must outlive any one capture
                entry = {"flops": 0.0, "bytes_accessed": 0.0,
                         "argument_bytes": 0, "output_bytes": 0,
                         "peak_temp_bytes": 0, "generated_code_bytes": 0,
                         "mesh_devices": 1, "comm": {},
                         "error": f"{type(e).__name__}: {e}",
                         "captured_at_unix": round(time.time(), 3)}
            with self._cv:
                self._programs[(op, blab)] = entry
                self._captures += 1
                if entry.get("error"):
                    self._capture_errors += 1
                self._pending -= 1
                self._cv.notify_all()

    def _analyze(self, program: ProgramSpec) -> dict:
        entry: dict = {
            "flops": 0.0, "bytes_accessed": 0.0,
            "argument_bytes": 0, "output_bytes": 0,
            "peak_temp_bytes": 0, "generated_code_bytes": 0,
            "mesh_devices": getattr(program, "mesh_devices", 1),
            "comm": {}, "error": "",
            "captured_at_unix": round(time.time(), 3),
        }
        try:
            import jax

            lowered = program.fn.lower(*program.args, **program.kwargs)
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca:
                entry["flops"] = float(ca.get("flops", 0.0))
                entry["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
            if self._memory_enabled():
                mem = lowered.compile().memory_analysis()
                if mem is not None:
                    entry["argument_bytes"] = int(
                        getattr(mem, "argument_size_in_bytes", 0))
                    entry["output_bytes"] = int(
                        getattr(mem, "output_size_in_bytes", 0))
                    entry["peak_temp_bytes"] = int(
                        getattr(mem, "temp_size_in_bytes", 0))
                    entry["generated_code_bytes"] = int(
                        getattr(mem, "generated_code_size_in_bytes", 0))
            if program.mesh_axis_sizes:
                jaxpr = jax.make_jaxpr(program.fn)(
                    *program.args, **program.kwargs)
                entry["comm"] = collective_comm_bytes(
                    jaxpr, program.mesh_axis_sizes, program.mesh_devices)
        except Exception as e:  # capture is advisory; record why it failed
            entry["error"] = f"{type(e).__name__}: {e}"
        return entry

    # ------------------------------------------------------------ readout
    def program_table(self) -> dict[tuple[str, str], dict]:
        with self._lock:
            return {k: {**dict(v), "launches": self._launches.get(k, 0),
                        "comm": dict(v["comm"])}
                    for k, v in self._programs.items()}

    def comm_for(self, op: str, bucket_label: str) -> dict[str, int]:
        with self._lock:
            e = self._programs.get((op, bucket_label))
            return dict(e["comm"]) if e else {}

    def comm_totals(self) -> dict[tuple[str, str], int]:
        """(op, collective) -> fleet wire bytes = per-launch bytes x
        launches of that program's bucket."""
        out: dict[tuple[str, str], int] = {}
        with self._lock:
            for (op, blab), e in self._programs.items():
                n = self._launches.get((op, blab), 0)
                for coll, b in e["comm"].items():
                    k = (op, coll)
                    out[k] = out.get(k, 0) + b * n
        return out

    # --------------------------------------------------------- HBM ledger
    def hbm_snapshot(self) -> dict:
        """One device-memory accounting view. Components are the
        accountable residents this process manages; `device` carries the
        backend's own memory_stats() where it exposes one (TPU runtimes
        do, CPU does not) as the cross-check -- device.bytes_in_use
        should be >= the accounted total, the gap being XLA runtime
        overhead plus anything staged outside these caches."""
        comps: dict[str, dict] = {}
        staged_bytes = live_bytes = code_bytes = 0
        try:
            from ..ops.stage import staged_cache_stats

            st = staged_cache_stats(max_entries=1)
            staged_bytes = int(st["bytes"])
            comps["staged_cache"] = {
                "bytes": staged_bytes, "entries": int(st["entries"]),
                "budget_bytes": int(st["budget_bytes"]),
            }
        except Exception:
            comps["staged_cache"] = {"bytes": 0, "error": "unavailable"}
        try:
            from ..ops.livestage import stager_device_bytes

            live_bytes, n_stagers = stager_device_bytes()
            comps["livestage"] = {"bytes": int(live_bytes),
                                  "stagers": int(n_stagers)}
        except Exception:
            comps["livestage"] = {"bytes": 0, "error": "unavailable"}
        with self._lock:
            code_bytes = sum(e["generated_code_bytes"]
                             for e in self._programs.values())
            peak_temp = max(
                (e["peak_temp_bytes"] for e in self._programs.values()),
                default=0)
            n_prog = len(self._programs)
        comps["compiled_programs"] = {
            "bytes": int(code_bytes), "programs": n_prog,
            "max_peak_temp_bytes": int(peak_temp),
        }
        total = staged_bytes + live_bytes + code_bytes
        with self._lock:
            if total > self._hbm_peak:
                self._hbm_peak = total
            peak = self._hbm_peak
        # every device's own view: staged columns are put on the default
        # device, so on a multi-chip host this says where data lives
        stats: list = []
        if device_identity()["count"]:  # never initialise a backend here
            import jax

            stats = [(d.id, d.memory_stats()) for d in jax.devices()]
        device = stats[0][1] if stats else None
        snap = {
            "components": comps,
            "accounted_bytes": int(total),
            "accounted_peak_bytes": int(peak),
            "device_memory_stats": device,
            "per_device_memory_stats": [{"id": i, **(st or {})}
                                        for i, st in stats],
        }
        if isinstance(device, dict) and "bytes_in_use" in device:
            snap["unaccounted_bytes"] = max(
                0, int(device["bytes_in_use"]) - int(total))
        return snap

    # ----------------------------------------------------------- metrics
    def metrics_lines(self) -> list[str]:
        """Exposition samples for /metrics (rendered through the app's
        strict-OpenMetrics pass like every kerneltel instrument)."""
        out: list[str] = []
        try:
            table = self.program_table()
            for (op, blab) in sorted(table):
                e = table[(op, blab)]
                lbl = f'op="{op}",bucket="{blab}"'
                out.append(f"tempo_program_flops{{{lbl}}} {e['flops']:g}")
                out.append(
                    f"tempo_program_bytes_accessed{{{lbl}}} "
                    f"{e['bytes_accessed']:g}")
                out.append(
                    f"tempo_program_peak_temp_bytes{{{lbl}}} "
                    f"{e['peak_temp_bytes']:g}")
            for (op, coll), b in sorted(self.comm_totals().items()):
                out.append(
                    f'tempo_mesh_comm_bytes_total{{collective="{coll}",'
                    f'op="{op}"}} {b:g}')
            hbm = self.hbm_snapshot()
            for comp, row in sorted(hbm["components"].items()):
                out.append(
                    f'tempo_hbm_bytes{{component="{comp}"}} '
                    f"{row.get('bytes', 0):g}")
            out.append(f"tempo_hbm_peak_bytes {hbm['accounted_peak_bytes']:g}")
            budget = hbm["components"].get("staged_cache", {}).get(
                "budget_bytes")
            if budget is not None:
                out.append(f"tempo_hbm_staged_budget_bytes {budget:g}")
            out += _DISK_CACHE_EVENTS.text()
            out += _COMPILE_SECONDS.text()
        except Exception:
            pass  # observability must never take /metrics down
        return out

    @staticmethod
    def help_entries() -> dict[str, str]:
        return {
            "tempo_program_flops":
                "XLA cost-analysis FLOPs per execution by op and shape bucket",
            "tempo_program_bytes_accessed":
                "XLA cost-analysis bytes accessed per execution by op/bucket",
            "tempo_program_peak_temp_bytes":
                "XLA peak temp allocation per execution by op/bucket",
            "tempo_mesh_comm_bytes":
                "static collective wire bytes x launches by collective and op",
            "tempo_hbm_bytes":
                "accounted device memory by component (staged_cache/"
                "livestage/compiled_programs)",
            "tempo_hbm_peak_bytes":
                "high-water mark of accounted device memory",
            "tempo_hbm_staged_budget_bytes":
                "device budget for the staged block-column cache",
            "tempo_kernel_compile_disk":
                "persistent compilation cache outcomes (hit = executable "
                "deserialized from disk, miss = fresh XLA compile)",
            "tempo_kernel_compile_seconds": _COMPILE_SECONDS.help,
        }

    # ------------------------------------------------------------- status
    def status_snapshot(self, drain_timeout: float = 1.0) -> dict:
        """The /status/cost payload: per-(op,bucket) static costs joined
        with kerneltel's measured wall times into achieved-vs-roofline
        utilization, per-collective comm bytes, the HBM ledger, the
        crossover ledger, and compile-cache state."""
        self.drain(drain_timeout)
        from .kerneltel import TEL

        kern = {(k["op"], k["bucket"]): k for k in TEL.snapshot(slow_k=0)["kernels"]}
        device = device_identity()
        # a kind the peaks table lacks has no roofline: "unknown", never
        # 0.0 and never another chip's figure
        peak_bps = HBM_PEAK_BPS.get(device["device_kind"], "unknown")
        programs = []
        table = self.program_table()
        for (op, blab) in sorted(table):
            e = table[(op, blab)]
            row = {"op": op, "bucket": blab, **{k: v for k, v in e.items()
                                               if k != "comm"}}
            krow = kern.get((op, blab))
            calls = krow["calls"] if krow else 0
            dev_s = krow["device_seconds"] if krow else 0.0
            if calls and dev_s > 0:
                per_call = dev_s / calls
                row["measured_calls"] = calls
                row["measured_s_per_call"] = round(per_call, 9)
                row["achieved_flops_per_s"] = round(e["flops"] / per_call, 1)
                row["achieved_bytes_per_s"] = round(
                    e["bytes_accessed"] / per_call, 1)
                row["hbm_utilization"] = (
                    round(e["bytes_accessed"] / per_call / peak_bps, 6)
                    if peak_bps != "unknown" else "unknown")
            comm = e["comm"]
            if comm:
                row["comm_bytes_per_launch"] = dict(sorted(comm.items()))
            programs.append(row)
        comm_rows = [
            {"op": op, "collective": coll, "bytes_total": b}
            for (op, coll), b in sorted(self.comm_totals().items())
        ]
        from .costledger import ledger

        with self._lock:
            meta = {"captures": self._captures,
                    "capture_errors": self._capture_errors,
                    "pending": self._pending,
                    "enabled": self.enabled()}
        return {
            "device": device,
            "roofline_hbm_bytes_per_s": peak_bps,
            "programs": programs,
            "comm": comm_rows,
            "hbm": self.hbm_snapshot(),
            "ledger": ledger().to_dict(),
            "compile_cache": compile_cache_stats(),
            "capture": meta,
        }

    def reset(self) -> None:
        """Fresh state (tests). The worker thread survives; in-flight
        captures may still land rows after a reset -- tests drain first."""
        with self._cv:
            # discarded queue items will never reach the worker's
            # decrement: release their pending counts here or drain()
            # waits its full timeout forever after
            self._pending -= len(self._queue)
            self._queue.clear()
            self._programs.clear()
            self._launches.clear()
            self._captures = 0
            self._capture_errors = 0
            self._hbm_peak = 0
            self._cv.notify_all()


COST = CostModel()


# ------------------------------------------- start-up: device + compile cache


class NoAcceleratorError(RuntimeError):
    """jax found no accelerator and the CPU was not asked for."""


_device_lock = threading.Lock()
_device: dict | None = None


def check_device(platform: str, asked_platforms: str | None) -> None:
    """The start-up rule: a process that launches kernels runs on an
    accelerator, or on the CPU only when JAX_PLATFORMS put it first
    (`cpu`; a chip host's `tpu,cpu` does not ask for the CPU). jax's
    own silent fall-back to the host when it finds no chip is
    refused."""
    first = (asked_platforms or "").lower().split(",")[0].strip()
    if platform == "cpu" and first != "cpu":
        raise NoAcceleratorError(
            "no accelerator: jax found no TPU and fell back to the cpu "
            "backend. Run on a chip host (one process per chip), or set "
            "JAX_PLATFORMS=cpu to run on the host CPU explicitly.")


def resolve_device() -> dict:
    """Resolve the jax backend ONCE for this process and name it:
    {"platform", "device_kind", "count"} as jax reports them. Every
    entry point that launches kernels (the services/app targets)
    calls this before serving; the "listening" line and the status JSON
    repeat what it returned. Raises NoAcceleratorError per
    check_device."""
    global _device
    with _device_lock:
        if _device is None:
            import jax

            devs = jax.devices()
            check_device(devs[0].platform, jax.config.jax_platforms)
            _device = {"platform": devs[0].platform,
                       "device_kind": devs[0].device_kind,
                       "count": len(devs)}
        return dict(_device)


def device_identity() -> dict:
    """The resolved device for status payloads, WITHOUT initialising a
    backend: a role that launches no kernels (a distributor beside the
    process that owns the chip) reports unresolved rather than grabbing
    the device to answer a status page."""
    with _device_lock:
        if _device is None:
            return {"platform": "unresolved", "device_kind": "unresolved",
                    "count": 0}
        return dict(_device)


# JAX's own variable places the cache from outside; where it is unset
# the cache lives at one fixed path (the path is part of the cache key,
# so a directory that moves never hits).
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

from .metrics import Counter as _Counter  # noqa: E402

_DISK_CACHE_EVENTS = _Counter(
    "tempo_kernel_compile_disk_total",
    help="persistent compilation cache outcomes by event")

_cc_lock = threading.Lock()
_cc_state = {"enabled": False, "dir": "", "listener": False}


_COMPILE_SECONDS = _Counter(
    "tempo_kernel_compile_seconds_total",
    help="wall seconds in XLA backend compiles (or their disk-cache "
         "reads) by the op whose launch paid them")


def _on_jax_event(name: str, **kw) -> None:
    if name.endswith("/compilation_cache/cache_hits"):
        _DISK_CACHE_EVENTS.inc(labels='outcome="hit"')
    elif name.endswith("/compilation_cache/cache_misses"):
        _DISK_CACHE_EVENTS.inc(labels='outcome="miss"')


def _on_jax_duration(name: str, secs: float, **kw) -> None:
    """Compile wall time, credited to the op this thread launched last
    (a compile runs inside the launch that needed it); the capture
    worker's own second compile of each program is kept apart."""
    if name != "/jax/core/compile/backend_compile_duration":
        return
    from .kerneltel import TEL

    last = TEL.last_launch()
    op = ("costmodel_capture"
          if threading.current_thread().name == "costmodel"
          else last[0] if last else "unattributed")
    _COMPILE_SECONDS.inc(secs, labels=f'op="{op}"')


def enable_compile_cache(cache_dir: str = "") -> str:
    """Turn on jax's persistent (disk) compilation cache so a restarted
    process deserializes yesterday's executables instead of re-paying
    the first-compile storm. Where JAX_COMPILATION_CACHE_DIR is set jax
    already reads that directory and this never points it elsewhere;
    otherwise the cache goes to `cache_dir`, default
    DEFAULT_COMPILE_CACHE_DIR. Registers a jax.monitoring listener so
    disk hits vs fresh compiles are counted
    (tempo_kernel_compile_disk_total) -- kerneltel's compile counter
    cannot tell them apart (both look like a new program key). Must run
    before the first compile to cover it; later calls still cover every
    compile after them. Returns the directory in use ("" when it cannot
    be created: the process then runs uncached, with a warning)."""
    import jax
    from jax._src import compilation_cache as _jcc

    env_dir = os.environ.get(JAX_CACHE_ENV, "")
    use = env_dir or cache_dir or DEFAULT_COMPILE_CACHE_DIR
    with _cc_lock:
        if not env_dir:
            try:
                os.makedirs(use, exist_ok=True)
            except OSError as e:
                from .log import get_logger

                get_logger("costmodel").warning(
                    "persistent compile cache at %r unavailable: %s", use, e)
                return ""
            jax.config.update("jax_compilation_cache_dir", use)
            # the cache object latches its (possibly empty) dir on first
            # compile: a process that compiled anything before this call
            # must rebuild it or the new dir is ignored
            _jcc.reset_cache()
        # cache everything: the padded-bucket discipline keeps the
        # program population small, so entry-size/compile-time floors
        # would only punch holes in warm restarts
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        if not _cc_state["listener"]:
            jax.monitoring.register_event_listener(_on_jax_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            _cc_state["listener"] = True
        _cc_state["enabled"] = True
        _cc_state["dir"] = use
    return use


def disable_compile_cache() -> None:
    """Turn the persistent cache back off (tests that enabled it at a
    throwaway dir must not leave the process reading a deleted path)."""
    import jax
    from jax._src import compilation_cache as _jcc

    with _cc_lock:
        jax.config.update("jax_compilation_cache_dir", None)
        _cc_state["enabled"] = False
        _cc_state["dir"] = ""
    _jcc.reset_cache()


def enable_default_compile_cache() -> str:
    """The hook every jax-touching entry point runs once at import
    (ops/device.py): cache on at jax's variable or the fixed default."""
    with _cc_lock:
        if _cc_state["enabled"]:
            return _cc_state["dir"]
    return enable_compile_cache()


def compile_cache_stats() -> dict:
    with _cc_lock:
        st = dict(_cc_state)
    st.pop("listener", None)
    st["disk_hits"] = int(_DISK_CACHE_EVENTS.get(labels='outcome="hit"'))
    st["disk_misses"] = int(_DISK_CACHE_EVENTS.get(labels='outcome="miss"'))
    st["compile_seconds_by_op"] = {
        labels[len('op="'):-1]: round(v, 3)
        for labels, v in sorted(_COMPILE_SECONDS.snapshot().items())}
    return st
