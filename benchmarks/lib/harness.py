"""The general machinery of a cell: the seeded request lists, the closed and
open load loops, warm-up steps, timed events and the check of every answer.

Nothing here knows a configuration, a mix, a shape or a metric by name: a
mix file names streams and steps, a stream names shapes, and a shape is
`benchmarks/shapes/<name>.py`, found by name.
"""

from __future__ import annotations

import importlib
import json
import queue
import random
import sys
import threading
import time

import numpy as np

from .oracle import load_oracle
from .pushgen import PushLog, PushTemplate
from .server import Client

DRAIN_S = 90  # how long the window's last requests may take to come back
TRACE_WAIT_S = 240  # and the trace capture to be stopped, zipped and fetched


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_plugin(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


class Env:
    """What a shape may look at: the configuration, the corpus manifest,
    the oracles (mapped on first use) and the write path's push log."""

    def __init__(self, config: dict, mix: dict, manifest: dict, seed: int):
        self.config, self.mix, self.manifest, self.seed = config, mix, manifest, seed
        self.force_block: int | None = None
        self.used: dict = {}  # operands drawn so far (shapeutil.draw_unique)
        self._oracles: dict = {}
        self._ids: dict = {}
        self._lock = threading.Lock()
        self.push_log = PushLog()
        self.push_template = None
        p = mix.get("push")
        if p:
            self.push_template = PushTemplate(seed, p["traces"], p["spans"],
                                              p["traces_per_bucket"])

    def oracle(self, block: int):
        with self._lock:
            if block not in self._oracles:
                self._oracles[block] = load_oracle(
                    self.manifest["blocks"][block]["oracle"])
            return self._oracles[block]

    def block_ids(self, block: int):
        with self._lock:
            if block not in self._ids:
                self._ids[block] = np.load(
                    self.manifest["blocks"][block]["oracle"] + "/ids.npy")
            return self._ids[block]

    def blocks(self, tenant: str | None = None) -> list[dict]:
        """One tenant's entries of the manifest, newest first, each under
        its own `index` into `manifest["blocks"]`: the tenant named, else
        the one a request that names none addresses (the corpus's first;
        every block where the configuration lists no tenants)."""
        first = self.manifest.get("tenant")
        tenant = tenant or first
        return [b for b in self.manifest["blocks"]
                if b.get("tenant", first) == tenant]

    def spans_covered(self, op: dict) -> int:
        """Spans of the blocks a request's start/end covers, by the corpus
        manifest (not by the server's word): the blocks of the tenant the
        request names in `op["tenant"]`, if it names one."""
        if "start" not in op:
            return 0
        return sum(b["n_spans"] for b in self.blocks(op.get("tenant"))
                   if b["start_s"] <= op["end"] and b["end_s"] >= op["start"])


# ------------------------------------------------------------ request lists
def shape_schedule(shapes: list[dict], n: int) -> list[int]:
    """Which shape the i-th request takes: at every step the shape furthest
    behind its weight (ties to the first). The same interleaving for every
    seed, so two runs differ in operands and not in the mix they drew."""
    total = sum(s["weight"] for s in shapes)
    counts = [0] * len(shapes)
    out = []
    for i in range(n):
        deficits = [s["weight"] / total * (i + 1) - counts[k]
                    for k, s in enumerate(shapes)]
        k = max(range(len(shapes)), key=lambda j: (deficits[j], -j))
        counts[k] += 1
        out.append(k)
    return out


def build_ops(mix_name: str, stream: dict, env: Env, n: int | None = None) -> list[dict]:
    """The stream's request list, from the seed alone."""
    rnd = random.Random(f"{env.seed}-{mix_name}-{stream['name']}")
    n = n or stream["ops"]
    ops = []
    for i, k in enumerate(shape_schedule(stream["shapes"], n)):
        spec = stream["shapes"][k]
        op = load_plugin("shapes", spec["shape"]).build(
            rnd, env, spec.get("params", {}))
        op.update(shape=spec["shape"], i=i)
        ops.append(op)
    return ops


def due_times(mix_name: str, stream: dict, seed: int, n: int) -> list[float]:
    """Open loop: offsets from the start of the window, Poisson gaps (or
    even ones) at the stream's fixed rate, from the seed."""
    rnd = random.Random(f"{seed}-{mix_name}-{stream['name']}-arrivals")
    rate = stream["rate_per_s"]
    t, out = 0.0, []
    for _ in range(n):
        t += (rnd.expovariate(rate) if stream.get("arrivals", "poisson") == "poisson"
              else 1.0 / rate)
        out.append(t)
    return out


# ------------------------------------------------------------------- loops
class StreamState:
    """One stream's list behind one shared cursor, and what came back."""

    def __init__(self, mix_name: str, stream: dict, env: Env):
        self.spec, self.env = stream, env
        self.ops = build_ops(mix_name, stream, env)
        self.dues = (due_times(mix_name, stream, env.seed, len(self.ops))
                     if stream["loop"] == "open" else None)
        self.cursor = 0
        self.window_from = 0  # the cursor when warm-up ended
        self.dues_base = 0.0
        self.lock = threading.Lock()
        self.results: list[dict] = []
        self.skipped = 0  # requests a shape had nothing to ask for yet

    def take(self) -> dict | None:
        with self.lock:
            if self.cursor >= len(self.ops):
                return None
            op = self.ops[self.cursor]
            self.cursor += 1
            return op


def result_record(op: dict, phase: str, status: int, t_send: float,
                  t_done: float, *, due: float | None = None, body_bytes: int = 0,
                  data: bytes = b"", ok: bool | None = None, detail: str = "") -> dict:
    """What every request leaves behind; `ok` stays None until its answer
    has been checked against the oracle."""
    return {"op": op, "phase": phase, "status": status,
            "t_due": t_send if due is None else due, "t_send": t_send,
            "t_done": t_done, "body_bytes": body_bytes, "data": data,
            "ok": ok, "detail": detail}


def send(op: dict, env: Env, client: Client, phase: str,
         due: float | None = None) -> dict | None:
    """One request, timed; None when the shape has nothing to ask yet."""
    shape = load_plugin("shapes", op["shape"])
    req = shape.request(op, env)
    if req is None:
        return None
    method, path, body, headers = req
    t_send = time.perf_counter()
    status, data = client.request(method, path, body, headers)
    t_done = time.perf_counter()
    if hasattr(shape, "on_response"):
        shape.on_response(op, status, env)
    return result_record(op, phase, status, t_send, t_done, due=due,
                         body_bytes=len(body) if body else 0,
                         data=data if shape.KIND != "push" else b"")


def run_closed(st: StreamState, port: int, t_end: float, phase: str) -> list[threading.Thread]:
    def client_loop():
        cl = Client(port, timeout=st.spec.get("timeout_s", 120))
        while time.perf_counter() < t_end:
            op = st.take()
            if op is None:
                break
            res = send(op, st.env, cl, phase)
            if res is None:
                with st.lock:
                    st.skipped += 1
                time.sleep(0.05)
                continue
            with st.lock:
                st.results.append(res)
        cl.close()

    threads = [threading.Thread(target=client_loop, daemon=True)
               for _ in range(st.spec["clients"])]
    for t in threads:
        t.start()
    return threads


def run_open(st: StreamState, port: int, t0: float, t_end: float,
             phase: str) -> list[threading.Thread]:
    """A dispatcher hands each request to a pool of senders at its due
    time; latency counts from the due time, so a stall is charged to every
    request it delays."""
    work: queue.Queue = queue.Queue()

    def dispatcher():
        while True:
            with st.lock:
                at = st.cursor
            if at >= len(st.ops) or t0 + st.dues[at] - st.dues_base >= t_end:
                break
            due = t0 + st.dues[at] - st.dues_base
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            work.put((st.take(), due, time.perf_counter()))
        for _ in range(st.spec.get("senders", 16)):
            work.put(None)

    def sender():
        cl = Client(port, timeout=st.spec.get("timeout_s", 60))
        while True:
            item = work.get()
            if item is None:
                break
            op, due, t_disp = item
            res = send(op, st.env, cl, phase, due=due)
            with st.lock:
                if res is None:
                    st.skipped += 1
                else:
                    res["t_disp"] = t_disp  # when the generator let it go
                    st.results.append(res)
        cl.close()

    # a stream that warm-up already drew from starts its gaps afresh
    st.dues_base = st.dues[st.cursor - 1] if st.cursor else 0.0
    threads = [threading.Thread(target=dispatcher, daemon=True)] + [
        threading.Thread(target=sender, daemon=True)
        for _ in range(st.spec.get("senders", 16))]
    for t in threads:
        t.start()
    return threads


def run_window(streams: dict[str, StreamState], mix: dict, port: int,
               seconds: float, phase: str, extra_threads=()) -> tuple:
    """Every stream of the mix for `seconds`, timed events beside them.
    Returns (t0, t_end) on the perf_counter clock and what the events saw."""
    t0 = time.perf_counter()
    t_end = t0 + seconds
    threads = []
    for st in streams.values():
        if st.spec["loop"] == "closed":
            threads += run_closed(st, port, t_end, phase)
        else:
            threads += run_open(st, port, t0, t_end, phase)
    events: list[dict] = []

    def fire(ev):
        wait = t0 + ev["at_share"] * seconds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        cl = Client(port, timeout=120)
        status, data = cl.request(ev["method"], ev["path"])
        cl.close()
        events.append({"event": ev, "status": status, "phase": phase,
                       "ok": status in ev.get("expect", [200, 204]),
                       "at_s": time.perf_counter() - t0})

    for ev in mix.get("events", []):
        t = threading.Thread(target=fire, args=(ev,), daemon=True)
        t.start()
        threads.append(t)
    extras = [threading.Thread(target=fn, args=(t0, seconds), daemon=True)
              for fn in extra_threads]
    for t in extras:
        t.start()
    drain_by = t_end + DRAIN_S
    for t in threads:
        t.join(timeout=max(0.1, drain_by - time.perf_counter()))
    alive = sum(t.is_alive() for t in threads)
    if alive:
        log(f"{alive} load threads still waiting {DRAIN_S} s after the "
            "window: their requests count as failed")
    # the trace capture is not load: a server busy with pushes takes a
    # minute and more to stop the profiler and zip what it recorded
    for t in extras:
        t.join(timeout=TRACE_WAIT_S)
    return t0, t_end, events


# ----------------------------------------------------------------- warm-up
def warm_up(streams: dict[str, StreamState], mix: dict, env: Env, port: int,
            kernels_snapshot) -> list[dict]:
    """The mix file's warm-up steps, in order. Every answer is checked like
    a window's; the results come back tagged phase='warm'."""
    out: list[dict] = []
    mix_name = mix["name"]

    def one(op, cl):
        res = send(op, env, cl, "warm")
        if res is not None:
            out.append(res)
            log(f"warm {op['shape']} block={op.get('block')} "
                f"{(res['t_done'] - res['t_send']) * 1e3:.0f} ms HTTP {res['status']}")
        return res

    for step in mix.get("warmup", []):
        kind = step["step"]
        if kind == "per_block":
            # each listed shape once over each block (of the tenant the
            # step names, if any), the blocks side by side: all of them at
            # once, or `max_parallel` at a time where a long blocklist says so
            rnd = random.Random(f"{env.seed}-{mix_name}-warm")
            indices = [b["index"] for b in env.blocks(step.get("tenant"))]
            per_block: list[list[dict]] = [[] for _ in indices]
            for spec in step["shapes"]:
                mod = load_plugin("shapes", spec["shape"])
                for k, b in enumerate(indices):
                    env.force_block = b
                    op = mod.build(rnd, env, spec.get("params", {}))
                    op.update(shape=spec["shape"], i=-1)
                    per_block[k].append(op)
            env.force_block = None

            def block_loop(lists):
                cl = Client(port, timeout=600)
                for ops in lists:
                    for op in ops:
                        one(op, cl)
                cl.close()

            n_threads = min(len(per_block),
                            step.get("max_parallel") or len(per_block))
            threads = [threading.Thread(target=block_loop,
                                        args=(per_block[i::n_threads],))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        elif kind == "ops":
            st = streams[step["stream"]]
            cl = Client(port, timeout=600)
            sent = 0
            for _ in range(step["count"] * 20):
                if sent >= step["count"]:
                    break
                op = st.take()
                if op is None:
                    break
                if one(op, cl) is None:
                    time.sleep(0.1)
                else:
                    sent += 1
            cl.close()
        elif kind == "http":
            cl = Client(port, timeout=600)
            t = time.perf_counter()
            status, _ = cl.request(step["method"], step["path"])
            cl.close()
            log(f"warm {step['method']} {step['path']} HTTP {status} "
                f"{(time.perf_counter() - t) * 1e3:.0f} ms")
            out.append(result_record(
                {"shape": "http", "i": -1}, "warm", status, t, time.perf_counter(),
                ok=status in (200, 204), detail=f"{step['path']} HTTP {status}"))
        elif kind == "sleep":
            time.sleep(step["seconds"])
        elif kind == "burst":
            # `size` same-shape searches at once, repeated until the fused
            # programs have launched (or `max_rounds`): whether window-mates
            # meet is up to the host's scheduling
            mod_name = step["shape"]
            rnd = random.Random(f"{env.seed}-{mix_name}-burst")
            for rnd_no in range(step["max_rounds"]):
                env.force_block = step.get("block", 0)
                ops = []
                for _ in range(step["size"]):
                    op = load_plugin("shapes", mod_name).build(
                        rnd, env, step.get("params", {}))
                    op.update(shape=mod_name, i=-1)
                    ops.append(op)
                env.force_block = None
                gate = threading.Barrier(len(ops))

                def fire(op):
                    cl = Client(port, timeout=600)
                    cl.conn.connect()
                    gate.wait(timeout=60)
                    one(op, cl)
                    cl.close()

                threads = [threading.Thread(target=fire, args=(op,)) for op in ops]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                wait_until = time.monotonic() + step.get("settle_s", 180)
                while True:
                    launched = {k["op"] for k in kernels_snapshot()["kernels"]}
                    fused = any(op in launched for op in step["until_any"])
                    if (not fused or all(op in launched for op in step.get("then_all", []))
                            or time.monotonic() > wait_until):
                        break
                    time.sleep(1)
                log(f"warm burst {rnd_no + 1}: fused launch seen = {fused}")
                if fused:
                    break
        else:
            raise ValueError(f"unknown warm-up step {kind!r}")
    return out


# ------------------------------------------------------------------- checks
def check_all(results: list[dict], env: Env, threads: int = 4) -> None:
    """Every answer against the oracle, off the clock."""
    todo: queue.Queue = queue.Queue()
    for r in results:
        if r["ok"] is None:
            todo.put(r)

    def work():
        while True:
            try:
                r = todo.get_nowait()
            except queue.Empty:
                return
            try:
                ok, detail = load_plugin("shapes", r["op"]["shape"]).check(
                    r["op"], r["status"], r["data"], env)
            except Exception as e:  # a check that cannot run is a failure
                ok, detail = False, f"check raised {type(e).__name__}: {e}"
            r["ok"], r["detail"], r["data"] = bool(ok), detail, b""

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
