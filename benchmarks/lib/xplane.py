"""Reduce a jax.profiler trace (.xplane.pb, or the zip the server's
/debug/profile/device publishes) to: the traced window, the seconds in which
an operation ran on each device, device time per XLA module and per op
family (module_ops.json), the modules that took most time and the longest
idle gaps with the host event that overlaps each most.

The traced window is the seconds the profiler was asked for, from the first
recorded event: what a file holds after that is the profiler stopping. Its
Python tracer then freezes every thread of the server while it converts its
events (1.9 s after a 3 s session, ~10 s after an 8 s one; chip traces,
PR 22), and a few events trail the freeze. Counting that stretch as window
would report the profiler's idle time as the system's.

Runs as a script in a process of its own with JAX_PLATFORMS=cpu, after the
server has exited: reading the file needs `jax.profiler.ProfileData`, and
the benchmark's parent never imports jax.

    python benchmarks/lib/xplane.py <trace.zip|.xplane.pb> <out.json> [asked seconds]
"""

from __future__ import annotations

import json
import os
import re
import sys
import zipfile

HERE = os.path.dirname(os.path.abspath(__file__))
MIN_HOST_EVENT_NS = 20_000  # shorter host events cannot own a gap worth naming


def merge(intervals):
    """Sorted, non-overlapping union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def family_of(module: str, families) -> str:
    for pattern, fam in families:
        if re.search(pattern, module):
            return fam
    return module


def clean_module(name: str) -> tuple[str, str]:
    """'jit_run(123)' -> ('run', 'jit_run(123)')."""
    base = re.sub(r"\(\d+\)$", "", name)
    base = re.sub(r"^jit_", "", base)
    return base, name


def gap_owner(gap, host_events, wait_names) -> str:
    """The host event that overlaps the gap most; among equals (frames of
    one stack all cover it) the shortest, i.e. the innermost."""
    gs, ge = gap
    best, best_key = "unattributed", None
    for s, e, name in host_events:
        ov = min(e, ge) - max(s, gs)
        if ov <= 0 or any(name.endswith(w) for w in wait_names):
            continue
        key = (round(ov / (ge - gs), 2), -(e - s))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def clip(events, t_end):
    """(name, start, duration) events cut at t_end; those after it dropped."""
    return [(n, s, min(d, t_end - s)) for n, s, d in events if s < t_end]


def reduce_planes(planes, ops_table: dict, asked_s: float | None = None) -> dict:
    """planes: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}] -- the shape ProfileData yields, as plain data. asked_s:
    the seconds the profiler was asked to record (None: all it holds)."""
    families = ops_table["families"]
    t_min, t_max = None, None
    for p in planes:
        for ln in p["lines"]:
            for _, s, d in ln["events"]:
                t_min = s if t_min is None else min(t_min, s)
                t_max = s + d if t_max is None else max(t_max, s + d)
    if t_min is None:
        return {"window_s": 0.0, "session_s": 0.0, "devices": [], "busy_s": 0.0,
                "modules": {}, "families": {}, "device_ops": [], "idle_gaps": []}
    session_s = (t_max - t_min) / 1e9
    if asked_s is not None:
        t_max = min(t_max, t_min + asked_s * 1e9)
    devices, modules, fams = [], {}, {}
    busy0 = None
    for p in planes:
        if not re.match(r"^/device:TPU:\d+$", p["name"]):
            continue
        by_line = {ln["name"]: clip(ln["events"], t_max) for ln in p["lines"]}
        ops = by_line.get("XLA Ops") or by_line.get("XLA Modules") or []
        busy = merge([(s, s + d) for _, s, d in ops])
        devices.append({"plane": p["name"], "n_ops": len(ops),
                        "busy_s": sum(e - s for s, e in busy) / 1e9})
        if busy0 is None:
            busy0 = busy
        for name, _, d in by_line.get("XLA Modules", []):
            base, raw = clean_module(name)
            m = modules.setdefault(raw, {"seconds": 0.0, "count": 0,
                                         "family": family_of(base, families)})
            m["seconds"] += d / 1e9
            m["count"] += 1
    for raw, m in modules.items():
        f = fams.setdefault(m["family"], {"seconds": 0.0, "count": 0})
        f["seconds"] += m["seconds"]
        f["count"] += m["count"]
    host_events = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                host_events += [(s, s + d, name.lstrip("$"))
                                for name, s, d in clip(ln["events"], t_max)
                                if d >= MIN_HOST_EVENT_NS]
    gaps = []
    if busy0 is not None:
        edges = [t_min] + [x for iv in busy0 for x in iv] + [t_max]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    top = sorted(modules.items(), key=lambda kv: -kv[1]["seconds"])[:10]
    return {
        "window_s": (t_max - t_min) / 1e9,
        "session_s": session_s,  # all the file holds, the profiler's stop included
        "devices": devices,
        "busy_s": (sum(d["busy_s"] for d in devices) / len(devices)
                   if devices else 0.0),
        "modules": modules, "families": fams,
        "device_ops": [[f"{m['family']} {raw}", m["seconds"]] for raw, m in top],
        "idle_gaps": [[gap_owner(g, host_events, ops_table["host_wait_names"]),
                       (g[1] - g[0]) / 1e9] for g in gaps],
    }


def read_planes(path: str):
    """The trace file as plain data (needs jax, and only here)."""
    from jax.profiler import ProfileData

    if zipfile.is_zipfile(path):
        with zipfile.ZipFile(path) as z:
            name = next(n for n in z.namelist() if n.endswith(".xplane.pb"))
            data = ProfileData.from_serialized_xspace(z.read(name))
    else:
        data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        if not (p.name.startswith("/device:") or p.name.startswith("/host:")):
            continue
        lines = []
        for ln in p.lines:
            lines.append({"name": ln.name,
                          "events": [(e.name, float(e.start_ns),
                                      float(e.duration_ns)) for e in ln.events]})
        planes.append({"name": p.name, "lines": lines})
    return planes


def main(argv) -> int:
    src, dst = argv[1], argv[2]
    with open(os.path.join(HERE, "module_ops.json")) as f:
        table = json.load(f)
    asked_s = float(argv[3]) if len(argv) > 3 else None
    out = reduce_planes(read_planes(src), table, asked_s)
    with open(dst, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
