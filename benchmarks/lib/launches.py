"""Device time per launched kernel, by the program's own name for it.

Every dispatch runs inside a `tempo/kernel:launch` annotation that carries
`op` (PR 23: kerneltel `TEL.launch`). The device plane itself cannot say
which kernel a module is: `filter`, `timeseries`, `multiquery` and
`live_filter` are all `jit_run(<program id>)`, and the v5e plane's op events
carry no op-name stat that the `tempo.<op>` named scope could reach. The
runtime's own flow ids join the two, event by event and whatever the clocks
or the launch's wait for its outputs do:

    tempo/kernel:launch {op}                      the caller's thread
      > PJRT_LoadedExecutable_Execute linkage {_p}    inside it, same thread
    PJRT_LoadedExecutable_Execute {_c}            the same id
      > tpu::System::Execute {_p}                 inside it, same thread
    tpu::System::Execute=>IssueSequencedEvent {_c}   the same id, any thread
      > DoEnqueueProgram {run_id}                 inside it, same thread
    "XLA Modules" event {run_id}                  the device plane

A module whose chain breaks anywhere (a dispatch outside every launch, an
event the session's start cut off) is left out, not guessed.

As a script (needs jax's ProfileData, so a process of its own on the CPU
backend; the benchmark's parent never imports jax):

    python benchmarks/lib/launches.py <trace.zip|.xplane.pb> <out.json> [asked seconds]

`reduce_cell(ctx)` is what a metric reader calls: it finds the run's
trace.zip, runs the script once and caches the result in the ctx."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import zipfile

LAUNCH = "tempo/kernel:launch"
# host event name -> (its part in the chain, the stats that carry the id)
LINKS = {"PJRT_LoadedExecutable_Execute linkage": ("call", ("_pt", "_p")),
         "PJRT_LoadedExecutable_Execute": ("exec", ("_ct", "_c")),
         "tpu::System::Execute": ("issue", ("_pt", "_p")),
         "tpu::System::Execute=>IssueSequencedEvent": ("issued", ("_ct", "_c")),
         "DoEnqueueProgram": ("enqueue", ("run_id",))}


def ops_by_run(launches, events) -> dict:
    """launches: [(line, start_ns, end_ns, op)]; events: [(line, start_ns,
    end_ns, part, id)] of LINKS -> {run_id: op} down the chain above."""
    by: dict = {}
    for ev in events:
        by.setdefault(ev[3], []).append(ev)

    def holder(ev, outers):
        """The innermost of `outers` on ev's line that holds its start."""
        held = [o for o in outers if o[0] == ev[0] and o[1] <= ev[1] <= o[2]]
        return max(held, key=lambda o: o[1]) if held else None

    def down(inner, outer, op_of_outer):
        """{id of an `inner` event: op of the `outer` event that holds it}"""
        outers = [o for o in by.get(outer, []) if o[4] in op_of_outer]
        pairs = ((ev, holder(ev, outers)) for ev in by.get(inner, []))
        return {ev[4]: op_of_outer[o[4]] for ev, o in pairs if o is not None}

    calls = ((ev, holder(ev, launches)) for ev in by.get("call", []))
    op = {ev[4]: held[3] for ev, held in calls if held is not None}
    return down("enqueue", "issued", down("issue", "exec", op))


def group(launches, events, modules, asked_s: float | None = None) -> dict:
    """modules: [(start_ns, dur_ns, name, run_id)] from the device planes'
    "XLA Modules" -> {op: {"seconds", "launches", "programs"}}, cut at the
    seconds asked for (the file goes on while the session stops)."""
    if not modules:
        return {}
    t_first = min([m[0] for m in modules] + [l[1] for l in launches])
    t_end = t_first + asked_s * 1e9 if asked_s else float("inf")
    run_op = ops_by_run(launches, events)
    out: dict = {}
    for s, d, n, rid in modules:
        if s >= t_end or rid not in run_op:
            continue
        row = out.setdefault(run_op[rid], {"seconds": 0.0, "launches": 0, "programs": []})
        row["seconds"] += min(d, t_end - s) / 1e9
        row["launches"] += 1
        if n not in row["programs"]:
            row["programs"].append(n)
    return out


def read_trace(path: str):
    """-> (launches, events, modules) of a trace file (needs jax, and only here)."""
    from jax.profiler import ProfileData

    if zipfile.is_zipfile(path):
        with zipfile.ZipFile(path) as z:
            name = next(n for n in z.namelist() if n.endswith(".xplane.pb"))
            data = ProfileData.from_serialized_xspace(z.read(name))
    else:
        data = ProfileData.from_file(path)
    launches, events, modules = [], [], []
    line = 0
    for p in data.planes:
        host = p.name.startswith("/host:")
        if not host and not re.match(r"^/device:TPU:\d+$", p.name):
            continue
        for ln in p.lines:
            line += 1
            if not host and ln.name != "XLA Modules":
                continue
            for e in ln.events:
                span = (float(e.start_ns), float(e.start_ns + e.duration_ns))
                if not host:
                    modules.append((span[0], float(e.duration_ns), e.name,
                                    str(dict(e.stats).get("run_id"))))
                elif e.name == LAUNCH:
                    launches.append((line, *span, str(dict(e.stats).get("op"))))
                elif e.name in LINKS:
                    part, keys = LINKS[e.name]
                    st = dict(e.stats)
                    if all(k in st for k in keys):
                        events.append((line, *span, part,
                                       "/".join(str(st[k]) for k in keys)))
    return launches, events, modules


def reduce_cell(ctx: dict) -> dict | None:
    """{op: {seconds, launches, programs}} of the run's device trace, or
    None where there is no trace file or the reduction fails."""
    if "_launches" in ctx:
        return ctx["_launches"]
    ctx["_launches"] = None
    if not ctx.get("trace_span"):
        return None
    from . import corpus, harness

    # the cell is the one entry of BENCHMARK.json with this configuration
    # under this mix; its run directory holds the trace the harness fetched
    bench = harness.load_json(os.path.join(corpus.ROOT, "BENCHMARK.json"))
    cell = next((w["name"] for w in bench["workloads"]
                 if w["config"] == ctx["config"]["name"]
                 and w["traffic"] == ctx["mix"]["name"]), None)
    if cell is None:
        return None
    src = os.path.join(corpus.bench_dir("run", cell), "trace.zip")
    if not os.path.exists(src):
        return None
    dst = os.path.join(os.path.dirname(src), "launches.json")
    asked = ctx["trace_span"][1] - ctx["trace_span"][0]
    p = subprocess.run([sys.executable, os.path.abspath(__file__), src, dst, str(asked)],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=corpus.ROOT,
                       capture_output=True, text=True, timeout=600)
    if p.returncode != 0:
        return None
    with open(dst) as f:
        ctx["_launches"] = json.load(f)
    return ctx["_launches"]


def main(argv) -> int:
    asked_s = float(argv[3]) if len(argv) > 3 else None
    with open(argv[2], "w") as f:
        json.dump(group(*read_trace(argv[1]), asked_s), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
