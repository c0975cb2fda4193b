"""One process tree under one command: spawn, pin, ready, drain.

`--target scalable-single-binary` (services/app) is the reference's
scalable deployment (cmd/tempo/app/modules.go:42-58) mapped onto one
accelerator host: instance 0 runs every module and serves the port, and
one `--target=querier` child per further chip pulls jobs from its
frontend (modules/querier/worker/frontend_processor.go:57-80). A chip
belongs to one process, so each instance is pinned to its own chip
before its jax backend starts.

This module is the one place that knows how an app process is started
as a child (argv, environment, lifeline), how it is waited for and how
it is stopped; `fleet/harness.py` starts its CPU topologies through the
same helpers. Nothing here imports jax.
"""

from __future__ import annotations

import glob
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCALABLE_TARGET = "scalable-single-binary"
# worker threads per interpreter in a tree (none given on the command
# line): one interpreter lock serialises a process's host work, so a
# job is better stolen by an idle process than queued behind a sibling
# thread; instance 0 also serves HTTP, finds and the merge
TREE_WORKER_CONCURRENCY = 2


def pin_env(index: int, n: int) -> dict[str, str]:
    """The TPU runtime's per-process variables that give instance
    `index` of `n` on one host exactly one chip: a pure function, applied
    to a child's environment before it starts and to instance 0's own
    `os.environ` before its jax backend is created (libtpu reads them
    when the client is built, not when jax is imported).

    - `TPU_VISIBLE_CHIPS=<index>`: the one chip this process may open;
    - `TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1` and `TPU_PROCESS_BOUNDS=1,1,1`:
      a topology of one process with one chip, so the runtime neither
      waits for peers nor claims the host's other chips;
    - `TPU_PROCESS_ADDRESSES` / `TPU_PROCESS_PORT` / `CLOUD_TPU_TASK_ID`:
      each single-process "slice" gets a runtime port of its own
      (8476 + index), or the second process fails to bind the first's.

    Working set found on a 2x2 v5e host (PERF.md, PR 26)."""
    if not 0 <= index < n:
        raise ValueError(f"instance {index} of {n}")
    port = 8476 + index
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
    }


def visible_chips() -> int:
    """Accelerator chips this host shows, counted from its device nodes
    (no jax: counting with jax would open them all in this process)."""
    for pattern in ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*"):
        found = glob.glob(pattern)
        if found:
            return len(found)
    return 0


def on_cpu(env=None) -> bool:
    """Whether jax is held to the CPU backend (tests, the benchmark's
    rehearsal): instances are then plain processes and nothing is pinned."""
    env = os.environ if env is None else env
    return env.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_ready(port: int, timeout: float = 90.0, proc=None) -> None:
    """Block until GET /ready answers 200; a `proc` that exits first
    ends the wait at once."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(
                f"process for port {port} exited {proc.returncode} "
                "before /ready")
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/ready", timeout=1) as r:
                if r.status == 200:
                    return
        except (urllib.error.URLError, ConnectionError, OSError):
            pass
        time.sleep(0.2)
    raise TimeoutError(f"port {port} never became ready")


def spawn_app(args, env=None, log=None) -> subprocess.Popen:
    """Start `python -m tempo_tpu.services.app *args` as a child that
    dies with this process: it inherits the read end of a pipe whose
    write end only this process holds (`--lifeline.fd`), and end-of-file
    there -- this process is gone, however it went -- makes the child
    exit and release its chip. The write end rides on the Popen object."""
    r, w = os.pipe()
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "tempo_tpu.services.app", *args,
             "--lifeline.fd", str(r)],
            env=env, cwd=REPO_ROOT, pass_fds=(r,),
            stdout=log, stderr=subprocess.STDOUT if log is not None else None)
    except BaseException:
        os.close(w)
        raise
    finally:
        os.close(r)
    proc.lifeline = w  # closed by stop_procs / at this process's death
    return proc


def watch_lifeline(fd: int) -> None:
    """Child side of spawn_app: exit when the parent's end closes."""

    def watch():
        try:
            while os.read(fd, 1):
                pass
        except OSError:
            pass
        os._exit(1)  # no drain: the parent cannot be answered any more

    threading.Thread(target=watch, daemon=True, name="lifeline").start()


def stop_procs(procs, grace_s: float = 20.0) -> None:
    """SIGTERM every live process, wait, SIGKILL what is left."""
    live = [p for p in procs if p.poll() is None]
    for p in live:
        p.terminate()
    deadline = time.time() + grace_s
    for p in live:
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
    for p in procs:
        w = getattr(p, "lifeline", None)
        if w is not None:
            try:
                os.close(w)
            except OSError:
                pass
            p.lifeline = None


class QuerierTree:
    """Instance 0's supervisor of its querier children: spawns one per
    further instance, knows when all are attached, respawns one that
    died (once each) and stops them before the parent drains."""

    def __init__(self, cfg, n: int, frontend, kv_dir: str):
        self.cfg, self.n, self.frontend = cfg, n, frontend
        self.kv_dir = kv_dir
        self.pin = not on_cpu()
        self.children: dict[int, dict] = {}  # index -> {proc, port, id, respawned}
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="querier-tree")

    def worker_id(self, index: int) -> str:
        # independent of the port: a block's owner on the affinity ring
        # is then a function of its id and the instance count alone
        return f"querier-{index}"

    def _spawn(self, index: int) -> subprocess.Popen:
        cfg = self.cfg
        port = free_port()
        args = ["--target=querier", "--http.port", str(port),
                "--storage.path", cfg.storage_path,
                "--instance.id", self.worker_id(index),
                "--kv.dir", self.kv_dir,
                "--querier.frontend-address", f"http://127.0.0.1:{cfg.http_port}",
                "--querier.worker-concurrency", str(cfg.worker_concurrency)]
        if cfg.internal_token:
            args += ["--internal.token", cfg.internal_token]
        if cfg.compile_cache_dir:
            args += ["--compile-cache.dir", cfg.compile_cache_dir]
        if cfg.overrides_path:
            args += ["--overrides.path", cfg.overrides_path]
        if cfg.multitenancy:
            args += ["--multitenancy"]
        env = dict(os.environ)
        if self.pin:
            env.update(pin_env(index, self.n))
        proc = spawn_app(args, env=env)  # shares this process's log
        with self._lock:
            prev = self.children.get(index, {})
            self.children[index] = {
                "proc": proc, "port": port, "id": self.worker_id(index),
                "respawned": "proc" in prev}
        return proc

    def start(self) -> None:
        for i in range(1, self.n):
            self._spawn(i)
        self._thread.start()

    def _watch(self) -> None:
        from ..util.log import get_logger

        log = get_logger("proctree")
        while not self._stop.wait(0.2):
            for i, ch in list(self.children.items()):
                rc = ch["proc"].poll()
                if rc is None or ch.get("gone"):
                    continue
                # its leased jobs go back to the queue now, not when the
                # lease runs out; its blocks' owner is the next on the ring
                self.frontend.worker_lost(ch["id"])
                if ch["respawned"] or self._stop.is_set():
                    ch["gone"] = True
                    log.error("querier child died again; not respawned",
                              instance=ch["id"], exit_code=rc)
                    continue
                log.warning("querier child died; respawning once",
                            instance=ch["id"], exit_code=rc)
                stop_procs([ch["proc"]])  # reaped; closes its lifeline
                self._spawn(i)

    def instances(self) -> list[dict]:
        with self._lock:
            return [{"index": i, "id": ch["id"], "port": ch["port"],
                     "pid": ch["proc"].pid,
                     "alive": ch["proc"].poll() is None}
                    for i, ch in sorted(self.children.items())]

    def ready(self) -> bool:
        """Every querier alive, polled once since it (re)started and
        reported its device."""
        attached = self.frontend.attached_workers()
        return all(ch["alive"] and ch["id"] in attached
                   for ch in self.instances())

    def stop(self) -> None:
        self._stop.set()
        with self._lock:
            procs = [ch["proc"] for ch in self.children.values()]
        stop_procs(procs)


# --------------------------------------------------- the tree's status
def fetch(port: int, path: str, token: str = "", timeout: float = 30.0) -> bytes:
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
    if token:
        req.add_header("X-Tempo-Internal-Token", token)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def _row_key(row: dict) -> tuple:
    """What identifies a row of a status table: its string fields
    (`op` + `bucket`, `layer` + `engine` + `reason`, ...)."""
    return tuple((k, v) for k, v in sorted(row.items()) if isinstance(v, str))


def sum_status(parts: list):
    """The sum of status payloads of one shape: numbers add, dicts and
    tables of rows (lists of dicts, matched by their string fields)
    merge, anything else is the first part's. Ratios are recomputed by
    the caller from the summed counts."""
    first = parts[0]
    if isinstance(first, bool) or first is None:
        return first
    if isinstance(first, (int, float)):
        return sum(p for p in parts if isinstance(p, (int, float))
                   and not isinstance(p, bool))
    if isinstance(first, dict):
        keys = list(first)
        for p in parts[1:]:
            if isinstance(p, dict):
                keys += [k for k in p if k not in keys]
        return {k: sum_status([p[k] for p in parts
                               if isinstance(p, dict) and k in p])
                for k in keys}
    if isinstance(first, list):
        rows = [r for p in parts if isinstance(p, list) for r in p]
        if rows and all(isinstance(r, dict) for r in rows):
            by_key: dict = {}
            for r in rows:
                by_key.setdefault(_row_key(r), []).append(r)
            if len(by_key) < len(rows) or len(parts) == 1:
                return [sum_status(rs) for rs in by_key.values()]
        return rows
    return first


# the sections of /status/kernels that are cumulative counters or sizes
# and so add up over the instances of a tree; the rest (slow-query log,
# native codec state, per-tenant tables) stays instance 0's own
SUMMED_SECTIONS = ("kernels", "jit_cache", "staging", "routing", "batching",
                   "compile_cache", "staged_cache", "stages",
                   "stages_at_session", "affinity", "hedging", "retries",
                   "dispatch", "range", "stream", "mesh_batch", "interp",
                   "caching")


def tree_kernel_status(own: dict, others: list[tuple[dict, dict]]) -> dict:
    """/status/kernels of a process tree: `own` is instance 0's payload,
    `others` [(instance row, that instance's payload or {})]. Counters
    are the sum over instances, `device` says the chips the tree owns,
    and `instances` lists each one's own device, staged cache and
    totals beside."""
    out = dict(own)
    snaps = [own] + [s for _, s in others if s]
    for sec in SUMMED_SECTIONS:
        parts = [s[sec] for s in snaps if s.get(sec) is not None]
        if parts:
            out[sec] = sum_status(parts)
    st = out.get("staging") or {}
    if st.get("rows_real_total"):
        st["padding_waste_ratio"] = round(
            st["rows_padded_total"] / st["rows_real_total"], 4)
    for row in ((out.get("affinity") or {}).get("staged_by_placement")
                or {}).values():
        n = row["hits"] + row["misses"]
        row["hit_rate"] = round(row["hits"] / n, 4) if n else 0.0
    cc = out.get("compile_cache")
    if isinstance(cc, dict) and isinstance(own.get("compile_cache"), dict):
        for k, v in own["compile_cache"].items():
            if isinstance(v, (str, bool)):
                cc[k] = v
    dev = dict(own["device"])
    dev["count"] = sum(s["device"]["count"] for s in snaps)
    out["device"] = dev

    def row(info: dict, s: dict) -> dict:
        return {**info, "device": s.get("device"),
                "staged_cache": {k: (s.get("staged_cache") or {}).get(k)
                                 for k in ("entries", "bytes", "budget_bytes")},
                "jit_cache": s.get("jit_cache"),
                "staging": s.get("staging")}

    out["instances"] = ([row({"index": 0, "id": "local", "alive": True}, own)]
                        + [row(info, s) for info, s in others])
    return out


# ------------------------------------------------ one trace of the tree
def merge_xspaces(spaces: list[bytes]) -> bytes:
    """One profiler file (tsl XSpace: `planes` = field 1, a plane's `name`
    = field 2) for a tree, from one per instance in instance order.
    Serialized protobuf messages concatenate, so planes are copied whole;
    every instance sees its one chip as `/device:TPU:0`, so instance i's
    device planes are re-emitted with the name `/device:TPU:<i>`. Host
    planes are instance 0's alone: an idle gap of chip 0 is then owned by
    what instance 0's own threads did, and the runtime's flow ids, which
    restart in every process, join launches to modules within one
    process only."""
    from ..wire import pbwire as pb

    out = bytearray()
    for i, space in enumerate(spaces):
        for num, wt, val in pb.iter_fields(space):
            if num != 1 or wt != pb.WT_LEN:  # hostnames, errors, warnings
                if i == 0 and wt == pb.WT_LEN:
                    pb.write_bytes_field(out, num, val)
                continue
            fields = list(pb.iter_fields(val))
            name = next((v.decode("utf-8", "replace") for n, w, v in fields
                         if n == 2 and w == pb.WT_LEN), "")
            if not name.startswith("/device:TPU:"):
                if i == 0:
                    pb.write_bytes_field(out, 1, val)
                continue
            if i:
                rest = name[len("/device:TPU:"):].lstrip("0123456789")
                plane = bytearray()
                for n, w, v in fields:
                    if n == 2 and w == pb.WT_LEN:
                        pb.write_string_field(plane, 2, f"/device:TPU:{i}{rest}")
                    elif w == pb.WT_LEN:
                        pb.write_bytes_field(plane, n, v)
                    else:
                        pb.write_varint_field(plane, n, v)
                val = bytes(plane)
            pb.write_bytes_field(out, 1, val)
    return bytes(out)
