"""Continuous profiling plane: where did the host CPU and lock time go.

Kerneltel (util/kerneltel) answers *which op* was slow and the
self-trace timelines (services/selftrace) answer *which stage*; this
module answers *where inside a stage* the host CPU went -- the missing
layer for tail-latency work, playing the role the reference gets for
free from Go's pprof (SURVEY.md 5.1, cmd/tempo/main.go mutex-profile
flag). Four faces, all advisory (nothing here may fail or perturb a
query; profiling off means bit-identical outputs and zero added
kernel launches):

  * an ALWAYS-ON low-rate background sampler (default ~19 Hz --
    deliberately co-prime with common 10/20/100 Hz periodic work so it
    can't alias against it; TEMPO_PROFILE_HZ, 0 = off) over
    sys._current_frames(). Each sample is attributed to a COMPONENT
    (innermost tempo_tpu frame: ops/db/frontend/ingester/...) and,
    via a thread registry maintained by kerneltel's
    set_active_trace/reset_active_trace, to the ACTIVE QUERY's
    self-trace id. Samples aggregate into a bounded folded-stack
    table (tempo_profile_samples_total{component}, /status/profile
    top stacks, flamegraph-ready folded text) and a time-bounded
    ring buffer that slow-query auto-capture snapshots.
  * ON-DEMAND captures: sample_cpu() is the /debug/profile burst
    profiler (high rate, bounded seconds, text or folded output) and
    capture_device_profile() wraps jax.profiler's trace into a
    downloadable artifact -- both publish through the ArtifactStore.
  * LOCK-CONTENTION profiling: timed_lock()/timed_rlock() factories
    return plain threading locks until TEMPO_LOCK_PROFILE=1 arms the
    TimedLock/TimedRLock wrappers (resolved at lock creation, so the
    unarmored hot path pays literally nothing). Armed wrappers record
    contended waits into tempo_lock_wait_seconds{lock} with self-trace
    exemplars; the hot locks the concurrency lint already catalogs
    (stage LRU, batchexec window, livestage tail, frontend queue,
    breaker) create through these factories.
  * SLOW-QUERY AUTO-CAPTURE: kerneltel.record_query calls
    capture_slow_query when a query's latency crosses its SLO class
    p99 threshold (the same TEMPO_SLO_<CLASS>_P99_S knobs util/slo
    reads); the sampler ring's window for that query is snapshotted
    into a folded artifact whose id lands in the slow-query log next
    to the self-trace id -- closing the loop page -> /status/slo ->
    slow-query log -> timeline + profile.

Artifacts live in a bounded directory (atomic tmp+rename publish,
oldest-first pruning); `tempo-tpu-cli profile [cpu|device|lock|
artifact]` fetches and renders them.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque

from . import runtimestats
from .metrics import Counter, Histogram

PROFILE_HZ_ENV = "TEMPO_PROFILE_HZ"
PROFILE_DIR_ENV = "TEMPO_PROFILE_DIR"
LOCK_PROFILE_ENV = "TEMPO_LOCK_PROFILE"

# ~19 Hz: low enough to stay invisible (<2% on the concurrent search
# bench), prime so it can't phase-lock with 10/20/100 Hz periodic work
DEFAULT_HZ = 19.0
MAX_STACK_DEPTH = 48  # frames kept per sample (innermost wins)
MAX_STACKS = 2048     # distinct folded stacks before overflow folding
RING_SECONDS = 120.0  # how far back slow-query capture can reach
RING_MAX = 16384      # hard cap regardless of hz
CAPTURE_MIN_GAP_S = 0.25  # slow-query capture stampede guard

# lock waits run from sub-us uncontended neighborhoods to whole-second
# convoy stalls; only CONTENDED acquisitions are observed
LOCK_WAIT_BUCKETS = (1e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3,
                     5e-3, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)

# the SLO latency classes build_default_slo registers (services/app):
# op -> (env var, default p99 seconds). Unknown ops use the search
# threshold -- every frontend query class is listed here today.
SLOW_THRESHOLDS = {
    "traces": ("TEMPO_SLO_TRACES_P99_S", 1.0),
    "search": ("TEMPO_SLO_SEARCH_P99_S", 2.5),
    "search_stream": ("TEMPO_SLO_STREAM_P99_S", 5.0),
    "metrics": ("TEMPO_SLO_METRICS_P99_S", 10.0),
}


class ProfilerUnavailable(RuntimeError):
    """A capture backend (jax device profiler, artifact store) is not
    usable in this process; endpoints surface it as 503, not 500."""


def slow_threshold(op: str) -> float:
    env, default = SLOW_THRESHOLDS.get(op, SLOW_THRESHOLDS["search"])
    try:
        return float(os.environ.get(env, "") or default)
    except ValueError:
        return default


# ------------------------------------------------------------ stack walk

_PKG_MARK = f"{os.sep}tempo_tpu{os.sep}"


def _component_of_file(filename: str) -> str:
    """tempo_tpu-relative component of one frame's file: services and
    util resolve to the module (frontend, kerneltel, ...), subpackages
    to their name (ops, db, block, ...), top-level modules to their
    stem (vulture)."""
    i = filename.rfind(_PKG_MARK)
    if i < 0:
        return ""
    parts = filename[i + len(_PKG_MARK):].split(os.sep)
    if len(parts) == 1:
        stem = parts[0][:-3] if parts[0].endswith(".py") else parts[0]
        return stem or "tempo_tpu"
    if parts[0] in ("services", "util"):
        stem = parts[1][:-3] if parts[1].endswith(".py") else parts[1]
        return stem
    return parts[0]


def _walk_frame(frame, with_line: bool = False) -> tuple[str, list[str]]:
    """(component, frames outermost->innermost) for one thread's frame.
    Component = the innermost tempo_tpu frame's home; raw f_code walk
    (no traceback machinery) so the sampler stays cheap."""
    frames: list[str] = []
    component = ""
    f = frame
    depth = 0
    while f is not None and depth < MAX_STACK_DEPTH:
        code = f.f_code
        fname = code.co_filename
        short = fname.rsplit(os.sep, 1)[-1]
        if with_line:
            frames.append(f"{short}:{f.f_lineno} {code.co_name}")
        else:
            frames.append(f"{short}:{code.co_name}")
        if not component:
            component = _component_of_file(fname)
        f = f.f_back
        depth += 1
    frames.reverse()
    return component, frames


# --------------------------------------------------------- artifact store


class ArtifactStore:
    """Bounded on-disk profile-artifact store. Publish is atomic
    (tmp + os.replace: a reader never sees a torn artifact), pruning is
    oldest-first by both file count and cumulative bytes. Ids are flat
    filenames; get() rejects anything path-shaped."""

    def __init__(self, root: str, max_files: int = 64,
                 max_bytes: int = 128 << 20):
        self.root = root
        self.max_files = max(1, int(max_files))
        self.max_bytes = max(1 << 20, int(max_bytes))
        self._lock = threading.Lock()
        os.makedirs(root, exist_ok=True)

    def put(self, kind: str, data: bytes, suffix: str = ".bin") -> str:
        aid = (f"{kind}-{int(time.time() * 1000):013d}-"
               f"{os.urandom(4).hex()}{suffix}")
        tmp = os.path.join(self.root, f".tmp-{aid}")
        with self._lock:
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, os.path.join(self.root, aid))
            self._prune_locked()
        return aid

    @staticmethod
    def _valid_id(aid: str) -> bool:
        return bool(aid) and not aid.startswith(".") and all(
            c.isalnum() or c in "._-" for c in aid) and ".." not in aid

    def get(self, aid: str) -> bytes | None:
        if not self._valid_id(aid):
            return None
        p = os.path.join(self.root, aid)
        if not os.path.isfile(p):
            return None
        try:
            with open(p, "rb") as f:
                return f.read()
        except OSError:
            return None

    def list(self) -> list[dict]:
        """Newest-first artifact index for /status/profile. Only plain
        files count: under the app the store root sits inside the
        storage path, whose poller may drop tenant-index DIRECTORIES
        beside the artifacts."""
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for name in names:
            if not self._valid_id(name):
                continue
            p = os.path.join(self.root, name)
            if not os.path.isfile(p):
                continue
            try:
                st = os.stat(p)
            except OSError:
                continue
            out.append({"id": name, "bytes": int(st.st_size),
                        "at_unix": round(st.st_mtime, 3)})
        out.sort(key=lambda a: -a["at_unix"])
        return out

    def _prune_locked(self) -> None:
        entries = []
        for name in os.listdir(self.root):
            p = os.path.join(self.root, name)
            if name.startswith(".tmp-"):
                # a crashed publish left a torn temp file behind
                try:
                    os.unlink(p)
                except OSError:
                    pass
                continue
            if not os.path.isfile(p):
                continue  # foreign directories are not ours to prune
            try:
                st = os.stat(p)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, name))
        entries.sort()  # oldest first
        total = sum(sz for _, sz, _ in entries)
        while entries and (len(entries) > self.max_files
                           or total > self.max_bytes):
            _, sz, name = entries.pop(0)
            try:
                os.unlink(os.path.join(self.root, name))
            except OSError:
                pass
            total -= sz


# ------------------------------------------------------------- profiler


class Profiler:
    """Process-wide continuous profiler (module singleton PROF)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.sampling = False  # read lock-free on kerneltel hot paths
        self._hz = 0.0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._stacks: dict[tuple[str, str], int] = {}
        self._overflow = 0
        self._total = 0
        self._ring: deque = deque()  # (wall, trace_hex, component, stack)
        self._ring_max = RING_MAX
        self._thread_traces: dict[int, str] = {}
        self._missing: set[int] = set()  # two-cycle tag-prune memory
        self._store: ArtifactStore | None = None
        self._last_capture = 0.0
        self.samples = Counter(
            "tempo_profile_samples_total",
            help="background sampler thread-samples by component")
        self.slow_captures = Counter(
            "tempo_profile_slow_captures_total",
            help="slow-query profile artifacts auto-captured")

    # ------------------------------------------------------- lifecycle
    def ensure_sampler(self) -> bool:
        """Start the always-on sampler at the env-configured rate
        (TEMPO_PROFILE_HZ, default ~19; 0 = off). Idempotent -- the
        app calls this at start; with hz=0 it is a strict no-op, so
        the profiling-off differential holds trivially."""
        try:
            hz = float(os.environ.get(PROFILE_HZ_ENV, "") or DEFAULT_HZ)
        except ValueError:
            hz = DEFAULT_HZ
        if hz <= 0:
            return False
        return self.start(hz)

    def start(self, hz: float = DEFAULT_HZ) -> bool:
        with self._lock:
            if self.sampling:
                return True
            self._hz = min(max(float(hz), 0.1), 1000.0)
            self._ring_max = min(RING_MAX,
                                 max(512, int(self._hz * RING_SECONDS)))
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="tempo-profiler")
            self.sampling = True
        self._thread.start()
        return True

    def stop(self) -> None:
        with self._lock:
            if not self.sampling:
                return
            self.sampling = False
            t = self._thread
            self._thread = None
        self._stop.set()
        if t is not None:
            t.join(timeout=2.0)

    def reset(self) -> None:
        """Fresh aggregate state (tests). The artifact store and armed
        sampler survive; only counts/tables clear."""
        with self._lock:
            self._stacks = {}
            self._overflow = 0
            self._total = 0
            self._ring.clear()
            self._thread_traces = {}
            self._missing = set()
            self._last_capture = 0.0

    # ------------------------------------------------------ artifacts
    def configure_artifacts(self, root: str, max_files: int = 64,
                            max_bytes: int = 128 << 20) -> None:
        """Aim the artifact store. An explicit TEMPO_PROFILE_DIR env
        wins over programmatic defaults -- the operator aimed it."""
        root = os.environ.get(PROFILE_DIR_ENV, "") or root
        with self._lock:
            self._store = ArtifactStore(root, max_files=max_files,
                                        max_bytes=max_bytes)

    def _store_or_env(self) -> ArtifactStore | None:
        with self._lock:
            if self._store is None:
                env = os.environ.get(PROFILE_DIR_ENV, "")
                if env:
                    self._store = ArtifactStore(env)
            return self._store

    def put_artifact(self, kind: str, data: bytes, suffix: str = ".bin") -> str:
        """Publish bytes a caller assembled (a tree's merged device
        trace) in the store the captures use -> artifact id."""
        store = self._store_or_env()
        if store is None:
            raise ProfilerUnavailable("no profile artifact store configured")
        return store.put(kind, data, suffix=suffix)

    def artifact_bytes(self, aid: str) -> bytes | None:
        store = self._store_or_env()
        return store.get(aid) if store is not None else None

    def artifact_list(self) -> list[dict]:
        store = self._store_or_env()
        return store.list() if store is not None else []

    # ----------------------------------------------------- attribution
    def note_thread_trace(self, tid: int, trace_id) -> None:
        """Kerneltel parks/unparks the active self-trace for a thread
        here (set_active_trace/reset_active_trace run ON the executing
        thread, so the tid is authoritative). Empty id = unpark."""
        hexid = ""
        try:
            hexid = trace_id.hex() if trace_id else ""
        except AttributeError:
            pass
        with self._lock:
            if hexid:
                self._thread_traces[tid] = hexid
            else:
                self._thread_traces.pop(tid, None)

    # -------------------------------------------------------- sampling
    def _loop(self) -> None:
        period = 1.0 / self._hz
        me = threading.get_ident()
        due = time.perf_counter() + period
        while not self._stop.wait(period):
            # the wait is over at `due`; what passes until this line runs
            # is the wait for the interpreter (runtimestats.GIL_WAIT),
            # taken before the stack walk so that it holds none of it
            runtimestats.GIL_WAIT.observe(max(0.0, time.perf_counter() - due))
            try:
                self._sample_once(me)
            except Exception:
                pass  # the sampler must never take the process down
            due = time.perf_counter() + period

    def _sample_once(self, me: int) -> None:
        now = time.time()
        frames = sys._current_frames()
        rows: list[tuple[int, str, str]] = []
        for tid, frame in frames.items():
            if tid == me:
                continue
            component, stack = _walk_frame(frame)
            rows.append((tid, component, ";".join(stack)))
        per_component: dict[str, int] = {}
        with self._lock:
            tags = self._thread_traces
            # threads die with their tag still parked (rare: a trace
            # active at thread exit). Prune only after a tid is absent
            # TWO consecutive cycles: the frames snapshot above is
            # taken before the stack walk, so a thread that spawned
            # and parked its tag in between must not lose it mid-query
            for tid in list(tags):
                if tid in frames:
                    self._missing.discard(tid)
                elif tid in self._missing:
                    tags.pop(tid, None)
                    self._missing.discard(tid)
                else:
                    self._missing.add(tid)
            for tid, component, stack in rows:
                key = (component, stack)
                if key in self._stacks or len(self._stacks) < MAX_STACKS:
                    self._stacks[key] = self._stacks.get(key, 0) + 1
                else:
                    self._overflow += 1
                self._total += 1
                self._ring.append((now, tags.get(tid, ""), component, stack))
                per_component[component] = per_component.get(component, 0) + 1
            while len(self._ring) > self._ring_max:
                self._ring.popleft()
        for component, n in per_component.items():
            self.samples.inc(n, labels=f'component="{component or "other"}"')

    # --------------------------------------------------------- readout
    def folded(self, top_k: int = 0) -> str:
        """Flamegraph-collapsed text of the aggregate table: one
        `component;frame;...;frame count` line per distinct stack."""
        with self._lock:
            items = sorted(self._stacks.items(), key=lambda kv: -kv[1])
        if top_k:
            items = items[:top_k]
        lines = [f"{(comp or 'other')};{stack} {n}"
                 for (comp, stack), n in items]
        return "\n".join(lines) + ("\n" if lines else "")

    def status_snapshot(self, top_k: int = 15) -> dict:
        """The /status/profile payload."""
        with self._lock:
            total = self._total
            overflow = self._overflow
            hz = self._hz
            running = self.sampling
            ring_len = len(self._ring)
            tagged = len(self._thread_traces)
            items = sorted(self._stacks.items(), key=lambda kv: -kv[1])
            distinct = len(items)
        components = {}
        for labels, v in self.samples.snapshot().items():
            # labels is 'component="x"'
            name = labels.split('"')[1] if '"' in labels else labels
            components[name] = int(v)
        top = [{
            "component": comp or "other",
            "samples": n,
            "share": round(n / total, 4) if total else 0.0,
            "stack": stack.split(";")[-8:],
        } for (comp, stack), n in items[:top_k]]
        return {
            "sampler": {
                "running": running,
                "hz": hz,
                "samples_total": total,
                "distinct_stacks": distinct,
                "overflow_samples": overflow,
                "ring_samples": ring_len,
                "tagged_threads": tagged,
                "probe": runtimestats.probe_stats(),
                "components": components,
                "top_stacks": top,
            },
            "locks": lock_stats(),
            "slow_captures": int(self.slow_captures.get()),
            "artifacts": self.artifact_list()[:20],
        }

    # ------------------------------------------------ slow-query capture
    def capture_slow_query(self, op: str, seconds: float,
                           trace_id: str) -> str:
        """Snapshot the sampler ring's window for one just-finished slow
        query into a folded artifact; returns the artifact id ('' when
        not captured). Samples tagged with OTHER queries' traces are
        excluded; samples tagged with THIS query or untagged (pool legs
        whose contextvar never passed set_active_trace) stay."""
        if not self.sampling:
            return ""
        # threshold first: every finished query lands here when the
        # sampler is armed, and the fast-path exit must not touch the
        # profiler lock (_store_or_env) the sampler itself contends on
        threshold = slow_threshold(op)
        if threshold <= 0 or seconds < threshold:
            return ""
        store = self._store_or_env()
        if store is None:
            return ""
        now = time.time()
        with self._lock:
            if now - self._last_capture < CAPTURE_MIN_GAP_S:
                return ""
            self._last_capture = now
            cutoff = now - float(seconds) - 1.0 / max(self._hz, 0.1)
            window = [r for r in self._ring if r[0] >= cutoff]
        rows = [r for r in window if r[1] in ("", trace_id)]
        folded: dict[str, int] = {}
        matched = 0
        for _, tag, comp, stack in rows:
            line = f"{comp or 'other'};{stack}"
            folded[line] = folded.get(line, 0) + 1
            if trace_id and tag == trace_id:
                matched += 1
        body = "".join(
            f"{line} {n}\n"
            for line, n in sorted(folded.items(), key=lambda kv: -kv[1]))
        text = (
            "# tempo-tpu slow-query profile\n"
            f"# op={op} seconds={seconds:.4f} threshold={threshold:g} "
            f"self_trace_id={trace_id or '-'}\n"
            f"# captured_unix={now:.3f} window_samples={len(rows)} "
            f"query_tagged_samples={matched} hz={self._hz:g}\n"
            + body)
        try:
            aid = store.put("slowq", text.encode(), suffix=".folded")
        except OSError:
            return ""
        self.slow_captures.inc()
        return aid

    # ------------------------------------------------ on-demand capture
    def sample_cpu(self, seconds: float, hz: float = 200.0,
                   fmt: str = "text") -> str:
        """Burst statistical profile for /debug/profile: sample every
        thread's stack for `seconds` at `hz` and render the hottest
        stacks (text) or the full flamegraph-collapsed table
        (folded). The sampling thread itself is excluded."""
        seconds = min(max(float(seconds), 0.05), 30.0)
        hz = min(max(float(hz), 1.0), 1000.0)
        me = threading.get_ident()
        names = {t.ident: t.name for t in threading.enumerate()}
        counts: dict[tuple[str, str], int] = {}
        total = 0
        deadline = time.monotonic() + seconds
        period = 1.0 / hz
        with_line = fmt != "folded"
        while time.monotonic() < deadline:
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue
                _, stack = _walk_frame(frame, with_line=with_line)
                key = (names.get(tid, str(tid)), ";".join(stack))
                counts[key] = counts.get(key, 0) + 1
                total += 1
            time.sleep(period)
        ordered = sorted(counts.items(), key=lambda kv: -kv[1])
        if fmt == "folded":
            return "".join(f"{tname};{stack} {n}\n"
                           for (tname, stack), n in ordered)
        lines = [f"# sampling profile: {seconds:.1f}s at ~{hz:.0f} Hz, "
                 f"{total} thread-samples\n"]
        for (tname, stack), n in ordered[:25]:
            lines.append(f"\n--- {tname}: {n} samples "
                         f"({100.0 * n / max(1, total):.1f}%)\n")
            lines.extend(f"    {fr}\n" for fr in stack.split(";")[-12:])
        return "".join(lines)

    def capture_device_profile(self, seconds: float,
                               python: bool = False) -> tuple[str, dict]:
        """Record a jax.profiler trace for `seconds` while serving
        continues, zip the trace directory, publish it as an artifact.
        Returns (artifact_id, summary). Raises ProfilerUnavailable when
        the device profiler or the store can't run here.

        The session records the device planes, the XLA runtime's host
        events and the program's own `tempo/<layer>:<stage>` annotations
        (kerneltel TEL.stage). jax's Python tracer is OFF unless
        `python`: it slows pure-Python code ~14x while it records and
        freezes every thread for seconds when the session stops. The
        first event is `tempo/profile:session` with `unix_ns` and
        `seconds`: event times in the file are relative to the session's
        start, and this lays wall-clock spans on them."""
        import io
        import shutil
        import tempfile
        import zipfile

        store = self._store_or_env()
        if store is None:
            raise ProfilerUnavailable(
                "no profile artifact store configured "
                f"(set {PROFILE_DIR_ENV} or run under the app)")
        seconds = min(max(float(seconds), 0.1), 60.0)
        try:
            import jax
        except Exception as e:  # pragma: no cover - jax is baked in
            raise ProfilerUnavailable(f"jax unavailable: {e}")
        from .kerneltel import TEL

        tmpd = tempfile.mkdtemp(prefix="tempo-devprof-")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 1 if python else 0
            opts.host_tracer_level = 2
            stop_s = 0.0
            try:
                TEL.mark_session()
                jax.profiler.start_trace(tmpd, profiler_options=opts)
                with jax.profiler.TraceAnnotation(
                        "tempo/profile:session", unix_ns=time.time_ns(),
                        seconds=seconds, python=int(python)):
                    pass
                time.sleep(seconds)
            finally:
                t_stop = time.perf_counter()
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                stop_s = time.perf_counter() - t_stop
            buf = io.BytesIO()
            n_files = 0
            with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
                for root, _dirs, files in os.walk(tmpd):
                    for name in files:
                        p = os.path.join(root, name)
                        z.write(p, os.path.relpath(p, tmpd))
                        n_files += 1
            if n_files == 0:
                raise ProfilerUnavailable(
                    "device profiler produced no trace files")
            data = buf.getvalue()
        except ProfilerUnavailable:
            raise
        except Exception as e:
            raise ProfilerUnavailable(f"device trace failed: "
                                      f"{type(e).__name__}: {e}")
        finally:
            shutil.rmtree(tmpd, ignore_errors=True)
        aid = store.put("device", data, suffix=".zip")
        # one server-log line per session: how long stop_trace() took
        # (the Python tracer's conversion freezes every thread for it)
        from .log import get_logger

        get_logger("profiler").info(
            "device trace session stopped", seconds=seconds,
            stop_s=round(stop_s, 4), python_tracer=python)
        return aid, {"bytes": len(data), "files": n_files,
                     "seconds": seconds, "stop_s": round(stop_s, 4),
                     "python": python}


PROF = Profiler()


# -------------------------------------------------- lock-wait profiling

LOCK_WAIT = Histogram(
    "tempo_lock_wait_seconds", buckets=LOCK_WAIT_BUCKETS,
    help="contended lock acquisition wait by lock name (armed via "
         "TEMPO_LOCK_PROFILE; exemplars carry the waiting query's "
         "self-trace id)")
LOCK_ACQ_NAME = "tempo_lock_acquisitions_total"
LOCK_ACQ_HELP = ("timed-lock acquisitions by lock name and outcome "
                 "(fast/contended)")

# per-lock stats rows: [fast, contended, wait_sum_s, wait_max_s].
# A row is mutated only while HOLDING its wrapped lock (acquirers of
# the same lock are already serialized), so armed profiling never
# funnels independent hot locks through one shared stats mutex --
# contention measured stays contention the workload caused. The
# registry (name, row) list is append-only under its own lock
# (construction-time only) and retains rows past their lock's GC so
# the exported counters stay monotonic.
_rows_lock = threading.Lock()
_lock_rows: list[tuple[str, list]] = []
_LOCK_ROWS_MAX = 4096  # runaway lock creation folds into one row
_OVERFLOW_ROW: list = [0, 0, 0.0, 0.0]


def lock_profiling_armed() -> bool:
    return os.environ.get(LOCK_PROFILE_ENV, "") not in ("", "0")


def _exemplar_tid() -> str | None:
    try:
        from .kerneltel import TEL

        return TEL._exemplar_tid()
    except Exception:
        return None


def lock_stats() -> dict[str, dict]:
    """Aggregate per-name stats (several breakers share one label).
    Rows are read without their locks: torn int/float reads skew a
    stat by one sample at worst, never corrupt it."""
    with _rows_lock:
        rows = list(_lock_rows)
        if _OVERFLOW_ROW[0] or _OVERFLOW_ROW[1]:
            rows.append(("_overflow", _OVERFLOW_ROW))
    agg: dict[str, list] = {}
    for name, row in rows:
        a = agg.setdefault(name, [0, 0, 0.0, 0.0])
        a[0] += row[0]
        a[1] += row[1]
        a[2] += row[2]
        a[3] = max(a[3], row[3])
    return {
        name: {"acquisitions": a[0] + a[1], "contended": a[1],
               "wait_sum_s": round(a[2], 6),
               "wait_max_s": round(a[3], 6)}
        for name, a in sorted(agg.items())
    }


class TimedLock:
    """threading.Lock wrapper timing CONTENDED acquisitions. The fast
    path is one non-blocking try plus an increment of the lock's OWN
    stats row (made under the lock just taken -- no extra mutex, no
    clock read). Condition-compatible (acquire/release signatures
    match)."""

    _factory = staticmethod(threading.Lock)

    def __init__(self, name: str):
        self.name = name
        self._lock = self._factory()
        row: list = [0, 0, 0.0, 0.0]
        with _rows_lock:
            if len(_lock_rows) < _LOCK_ROWS_MAX:
                _lock_rows.append((name, row))
            else:
                row = _OVERFLOW_ROW  # lossy shared fallback, bounded
        self._row = row

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if self._lock.acquire(False):
            self._row[0] += 1  # holding the lock: serialized per lock
            return True
        if not blocking:
            return False
        t0 = time.perf_counter()
        ok = self._lock.acquire(True, timeout)
        if ok:
            wait_s = time.perf_counter() - t0
            row = self._row
            row[1] += 1
            row[2] += wait_s
            if wait_s > row[3]:
                row[3] = wait_s
            try:
                LOCK_WAIT.observe(wait_s, f'lock="{self.name}"',
                                  exemplar=_exemplar_tid())
            except Exception:
                pass  # wait telemetry must never wedge the lock
        return ok

    def release(self) -> None:
        self._lock.release()

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> "TimedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self._lock.release()
        return False

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<{type(self).__name__} {self.name!r} {self._lock!r}>"


class TimedRLock(TimedLock):
    """Reentrant variant: the owner's recursive re-acquire succeeds on
    the non-blocking fast path, so recursion is never timed as
    contention."""

    _factory = staticmethod(threading.RLock)

    def locked(self) -> bool:  # RLock has no locked(); answer truthfully
        if self._lock.acquire(False):
            self._lock.release()
            return False
        return True

    def _is_owned(self) -> bool:
        # Condition(RLock) consults _is_owned; the fallback probe
        # (acquire(0)) would RECURSE for the owner and misreport
        return self._lock._is_owned()


def timed_lock(name: str):
    """A lock for a cataloged hot critical section: the TimedLock
    wrapper when TEMPO_LOCK_PROFILE arms contention profiling, a raw
    threading.Lock otherwise (zero overhead, bit-identical paths)."""
    return TimedLock(name) if lock_profiling_armed() else threading.Lock()


def timed_rlock(name: str):
    return TimedRLock(name) if lock_profiling_armed() else threading.RLock()


# ------------------------------------------------------------ exposition


def _lock_acq_lines() -> list[str]:
    """Acquisition counters rendered from the per-lock stats rows (the
    hot path never touches a shared Counter lock; exposition derives
    the series at scrape time)."""
    out = []
    for name, s in lock_stats().items():
        fast = s["acquisitions"] - s["contended"]
        if fast:
            out.append(f'{LOCK_ACQ_NAME}{{lock="{name}",outcome="fast"}} '
                       f"{fast}")
        if s["contended"]:
            out.append(f'{LOCK_ACQ_NAME}{{lock="{name}",'
                       f'outcome="contended"}} {s["contended"]}')
    return out


def metrics_lines() -> list[str]:
    return (PROF.samples.text() + PROF.slow_captures.text()
            + LOCK_WAIT.text() + _lock_acq_lines())


def help_entries() -> dict[str, str]:
    return {
        "tempo_profile_samples": PROF.samples.help,
        "tempo_profile_slow_captures": PROF.slow_captures.help,
        "tempo_lock_wait_seconds": LOCK_WAIT.help,
        "tempo_lock_acquisitions": LOCK_ACQ_HELP,
    }
