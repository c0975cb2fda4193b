"""The corpus of a configuration: blocks on disk, their oracle columns and a
manifest, made from (--seed, block index) and kept in the checkout's
git-ignored `.benchdata/` so that a second run of the same seed links it.

Layout. A configuration's `corpus` may say two things beside its sizes;
absent, each means what the benchmark did before it could (PR 40):

- `tenants`: `[{"name": ..., "blocks": n}, ...]`, the block indices dealt
  in the list's order (default: one tenant, `single-tenant`, every block).
  The first tenant is the one a request that names none addresses.
- `blocks_per_window` k (default 1): a tenant's j-th block fills the hour of
  that tenant's window j // k, so k blocks end in the same one-hour
  compaction window (db/compactor.select_jobs keys on end // 3600 s) and a
  fresh storage directory holds one level-0 job of min(k, max_input_blocks)
  blocks a window and tenant. Every block keeps its own seed stream, so
  trace ids stay disjoint between blocks.

Workers build in batches: as many at once as `resolve_workers` admits under a
fixed bound on what they cost the host together, the same number on every
host and on both sides of a pair. No key sets it.

Dating. Window w starts at `top - (w + 1) * (3600 s + gap_s)`, `top` being
the top of the clock hour in which the corpus was made; a span lasts at most
1 s. So neighbouring windows are `gap_s - 1` seconds apart and every window
lies whole in the past; with one block a window a request's start/end can
select exactly one block and no two blocks share a compaction window. A
cached corpus older than `max_age_h` is made again: retention and "recent
past" stay true.

One worker process per block (this file run as a script), because a block
is ~11 s of numpy on one core.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import hashlib
import json
import os
import resource
import shutil
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))  # the checkout
TENANT = "single-tenant"  # services/app.DEFAULT_TENANT (multitenancy off)
HOUR_NS = 3600 * 1_000_000_000

# What one worker costs the host: its peak resident set plus the bytes it
# leaves on disk, which the chip machine's host counts against its memory too
# (eleven full-width workers at once, 22.9 GB resident by this line, were
# killed at the machine's 40 GiB: my chip-host run, PR 40, as PR 25's twelve
# were). A line through the two sizes the benchmark has, measured there on one
# worker each (PR 40): 10.35 M spans 2.076 GB resident + 1.025 GB written,
# 1.29 M spans 0.423 GB + 0.128 GB.
WORKER_BYTES_BASE = 190 << 20
WORKER_BYTES_PER_SPAN = 285
# Workers are admitted while their costs sum to no more than this: a fixed
# bound, not a share of what the host has free at the moment, so that a cell's
# worker count (and with it its `setup_s`) is the same whatever else the host
# is doing. Eight full-width workers at once took 24.6 GB of the one-chip
# machine's 45 GiB (it ends a command at 40 GiB); the four configurations
# the benchmark has cost 12.6 GB (4 full-width) and 18.2 GB (32 at an eighth).
BUILD_MEM_CAP = 24 << 30


def bench_dir(*parts: str) -> str:
    return os.path.join(ROOT, ".benchdata", *parts)


def sizes(config: dict, scale: str) -> dict:
    c = dict(config["corpus"])
    if scale == "tiny":
        c.update(config["tiny_corpus"])
    c["blocks"] = config["blocks"]
    return c


def _one_tenant(sz: dict) -> list[dict]:
    return [{"name": TENANT, "blocks": sz["blocks"]}]


def layout(sz: dict) -> list[dict]:
    """Each block's tenant and compaction window, by block index."""
    k = int(sz.get("blocks_per_window", 1))
    tenants = sz.get("tenants") or _one_tenant(sz)
    if k < 1 or sum(t["blocks"] for t in tenants) != sz["blocks"]:
        raise ValueError(f"corpus: blocks_per_window {k} / tenants {tenants} "
                         f"do not lay out {sz['blocks']} blocks")
    return [{"tenant": t["name"], "window": j // k}
            for t in tenants for j in range(t["blocks"])]


def window_base_ns(top_ns: int, window: int, gap_s: int) -> int:
    """Where a compaction window's hour begins (the module's docstring)."""
    return top_ns - (window + 1) * (HOUR_NS + gap_s * 1_000_000_000)


def cache_key(sz: dict) -> str:
    """What the blocks are made from, not a configuration's name: two
    configurations with the same sizes and layout share the files. At the
    default layout the key is what it was before a layout could be stated."""
    key = "b{blocks}-t{traces_per_block}x{spans_per_trace}-g{gap_s}".format(**sz)
    k = int(sz.get("blocks_per_window", 1))
    if k != 1:
        key += f"-w{k}"
    tenants = sz.get("tenants")
    if tenants and tenants != _one_tenant(sz):
        digest = hashlib.sha1(json.dumps(
            [[t["name"], t["blocks"]] for t in tenants]).encode()).hexdigest()[:8]
        key += f"-n{len(tenants)}x{digest}"
    return key


def resolve_workers(sz: dict) -> int:
    """How many workers build at once: as many as cost no more than
    `BUILD_MEM_CAP` together, at least one."""
    cost = WORKER_BYTES_BASE + WORKER_BYTES_PER_SPAN * (
        sz["traces_per_block"] * sz["spans_per_trace"])
    return max(1, min(sz["blocks"], BUILD_MEM_CAP // cost))


def _worker(argv: list[str]) -> int:
    return subprocess.call(argv, cwd=ROOT)


def run_pool(commands: list[list[str]], limit: int, run=_worker) -> list:
    """Every command as a process of its own, at most `limit` alive at once,
    in order. After the first that fails no further one starts (its exit
    code stays None); the ones running are waited for."""
    failed = threading.Event()

    def one(argv):
        if failed.is_set():
            return None
        rc = run(argv)
        if rc:
            failed.set()
        return rc

    with concurrent.futures.ThreadPoolExecutor(max(1, limit)) as pool:
        return list(pool.map(one, commands))


def ensure(config: dict, scale: str, seed: int, log=print) -> dict:
    """-> manifest of the corpus for (config, scale, seed), built if the
    cache holds none young enough."""
    sz = sizes(config, scale)
    plan = layout(sz)
    path = bench_dir("corpus", f"{cache_key(sz)}-{scale}-s{seed}")
    mpath = os.path.join(path, "manifest.json")
    if os.path.exists(mpath):
        with open(mpath) as f:
            manifest = json.load(f)
        age_h = (time.time() - manifest["created_unix"]) / 3600
        if age_h < config["corpus"]["max_age_h"]:
            log(f"corpus: cached {path} ({age_h:.2f} h old)")
            return manifest
    shutil.rmtree(path, ignore_errors=True)
    # a full-size corpus is ~4 GB with its oracle columns: keep the newest
    # `keep_corpora - 1` others, so that a check of many seeds cannot fill
    # the disk
    root = bench_dir("corpus")
    os.makedirs(root, exist_ok=True)
    others = sorted((os.path.join(root, d) for d in os.listdir(root)),
                    key=os.path.getmtime, reverse=True)
    for old in others[max(0, config["corpus"].get("keep_corpora", 2) - 1):]:
        shutil.rmtree(old, ignore_errors=True)
    os.makedirs(path)
    now_ns = time.time_ns()
    top_ns = now_ns - now_ns % HOUR_NS
    t0 = time.perf_counter()
    commands = []
    for b, at in enumerate(plan):
        base = window_base_ns(top_ns, at["window"], sz["gap_s"])
        commands.append(
            [sys.executable, os.path.abspath(__file__), "--out", path,
             "--block", str(b), "--seed", str(seed),
             "--traces", str(sz["traces_per_block"]),
             "--spans-per", str(sz["spans_per_trace"]),
             "--n-res", str(sz["resources"]),
             "--attrs-per-span", str(sz["attrs_per_span"]),
             "--base-time-ns", str(base),
             "--tenant", at["tenant"], "--window", str(at["window"])])
    workers = resolve_workers(sz)
    rcs = run_pool(commands, workers)
    if any(rc != 0 for rc in rcs):
        shutil.rmtree(path, ignore_errors=True)
        raise RuntimeError(f"corpus workers exited {rcs}")
    blocks = []
    for b in range(sz["blocks"]):
        with open(os.path.join(path, f"block{b}.json")) as f:
            blocks.append(json.load(f))
    manifest = {"path": path, "created_unix": time.time(), "top_ns": top_ns,
                "seed": seed, "scale": scale, "tenant": plan[0]["tenant"],
                "blocks": blocks,
                "total_spans": sum(b["n_spans"] for b in blocks)}
    tenants = list(dict.fromkeys(at["tenant"] for at in plan))
    if len(tenants) > 1:
        manifest["tenants"] = tenants
    seconds = time.perf_counter() - t0
    # the largest resident set any one worker reached: what the estimate in
    # `resolve_workers` is held against (ru_maxrss is in KiB)
    peak = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss << 10
    manifest["build"] = {"workers": workers, "seconds": seconds,
                         "worker_peak_rss_bytes": peak}
    with open(mpath, "w") as f:
        json.dump(manifest, f, indent=1)
    log(f"corpus: built {len(blocks)} blocks, {manifest['total_spans']} spans "
        f"in {seconds:.1f}s, {workers} workers at once, largest peak "
        f"{peak / 2**30:.2f} GiB -> {path}")
    return manifest


def link_store(manifest: dict, storage: str) -> None:
    """A fresh storage directory for one run: the corpus's block files
    hard-linked (copied where the file system refuses), nothing else. The
    backend writes objects by rename, so a link is never written through."""
    shutil.rmtree(storage, ignore_errors=True)
    src = os.path.join(manifest["path"], "store")

    def link(a, b):
        try:
            os.link(a, b)
        except OSError:
            shutil.copy2(a, b)

    shutil.copytree(src, storage, copy_function=link)


def build_block(args) -> None:
    """One block, its oracle columns and its `block{b}.json`. `args.tenant`
    and `args.window` are optional: the default tenant, a window of its own."""
    import numpy as np

    sys.path.insert(0, ROOT)
    from benchmarks.lib.oracle import save_oracle
    from benchmarks.lib.synth import synth_columns
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.util.testdata import write_synth_block  # the program's writer

    tenant = getattr(args, "tenant", None) or TENANT
    window = getattr(args, "window", None)
    if window is None:
        window = args.block
    rng = np.random.default_rng([args.seed, args.block])
    cols, strings, ids = synth_columns(
        rng, args.traces, args.spans_per, n_res=args.n_res,
        attrs_per_span=args.attrs_per_span, base_time_ns=args.base_time_ns)
    backend = LocalBackend(os.path.join(args.out, "store"))
    meta = write_synth_block(backend, tenant, cols, strings, ids)
    odir = os.path.join(args.out, "oracle", f"b{args.block}")
    save_oracle(odir, cols, strings, ids, args.spans_per, args.attrs_per_span)
    with open(os.path.join(args.out, f"block{args.block}.json"), "w") as f:
        json.dump({
            "index": args.block, "block_id": meta.block_id,
            "tenant": tenant, "window": window,
            "n_traces": int(ids.shape[0]), "spans_per": args.spans_per,
            "n_spans": int(cols["span.dur_us"].shape[0]),
            "size_bytes": int(meta.size_bytes),
            "base_time_ns": args.base_time_ns,
            "start_s": int(cols["span.start_ns"].min()) // 1_000_000_000,
            "end_s": int(cols["span.end_ns"].max()) // 1_000_000_000 + 1,
            "oracle": odir}, f)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--tenant")
    ap.add_argument("--window", type=int)
    for name in ("block", "seed", "traces", "spans-per", "n-res",
                 "attrs-per-span", "base-time-ns"):
        ap.add_argument("--" + name, type=int, required=True)
    build_block(ap.parse_args())
