"""The corpus of a configuration: blocks on disk, their oracle columns and a
manifest, made from (--seed, block index) and kept in the checkout's
git-ignored `.benchdata/` so that a second run of the same seed links it.

Dating. Block b's span starts fill the hour that begins at
`top - (b + 1) * (3600 s + gap_s)`, `top` being the top of the clock hour in
which the corpus was made; a span lasts at most 1 s. So neighbours are
`gap_s - 1` seconds apart, a request's start/end can select exactly one
block, and no two blocks end in the same one-hour compaction window
(db/compactor.select_jobs keys on end // 3600 s). A cached corpus older than
`max_age_h` is made again: retention and "recent past" stay true.

One worker process per block (this file run as a script), because a block
is ~11 s of numpy on one core and a cell has four.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))  # the checkout
TENANT = "single-tenant"  # services/app.DEFAULT_TENANT (multitenancy off)
HOUR_NS = 3600 * 1_000_000_000


def bench_dir(*parts: str) -> str:
    return os.path.join(ROOT, ".benchdata", *parts)


def sizes(config: dict, scale: str) -> dict:
    c = dict(config["corpus"])
    if scale == "tiny":
        c.update(config["tiny_corpus"])
    c["blocks"] = config["blocks"]
    return c


def ensure(config: dict, scale: str, seed: int, log=print) -> dict:
    """-> manifest of the corpus for (config, scale, seed), built if the
    cache holds none young enough."""
    sz = sizes(config, scale)
    # the blocks of two configurations with the same sizes are the same
    # files: the key is what they are made from, not a configuration's name
    key = "b{blocks}-t{traces_per_block}x{spans_per_trace}-g{gap_s}".format(**sz)
    path = bench_dir("corpus", f"{key}-{scale}-s{seed}")
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
    procs = []
    for b in range(sz["blocks"]):
        base = top_ns - (b + 1) * (HOUR_NS + sz["gap_s"] * 1_000_000_000)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--out", path,
             "--block", str(b), "--seed", str(seed),
             "--traces", str(sz["traces_per_block"]),
             "--spans-per", str(sz["spans_per_trace"]),
             "--n-res", str(sz["resources"]),
             "--attrs-per-span", str(sz["attrs_per_span"]),
             "--base-time-ns", str(base)], cwd=ROOT))
    rcs = [p.wait() for p in procs]
    if any(rcs):
        shutil.rmtree(path, ignore_errors=True)
        raise RuntimeError(f"corpus workers exited {rcs}")
    blocks = []
    for b in range(sz["blocks"]):
        with open(os.path.join(path, f"block{b}.json")) as f:
            blocks.append(json.load(f))
    manifest = {"path": path, "created_unix": time.time(), "top_ns": top_ns,
                "seed": seed, "scale": scale, "tenant": TENANT,
                "blocks": blocks,
                "total_spans": sum(b["n_spans"] for b in blocks)}
    with open(mpath, "w") as f:
        json.dump(manifest, f, indent=1)
    log(f"corpus: built {len(blocks)} blocks, {manifest['total_spans']} spans "
        f"in {time.perf_counter() - t0:.1f}s -> {path}")
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
    import numpy as np

    sys.path.insert(0, ROOT)
    from benchmarks.lib.oracle import save_oracle
    from benchmarks.lib.synth import synth_columns
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.util.testdata import write_synth_block  # the program's writer

    rng = np.random.default_rng([args.seed, args.block])
    cols, strings, ids = synth_columns(
        rng, args.traces, args.spans_per, n_res=args.n_res,
        attrs_per_span=args.attrs_per_span, base_time_ns=args.base_time_ns)
    backend = LocalBackend(os.path.join(args.out, "store"))
    meta = write_synth_block(backend, TENANT, cols, strings, ids)
    odir = os.path.join(args.out, "oracle", f"b{args.block}")
    save_oracle(odir, cols, strings, ids, args.spans_per, args.attrs_per_span)
    with open(os.path.join(args.out, f"block{args.block}.json"), "w") as f:
        json.dump({
            "index": args.block, "block_id": meta.block_id,
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
    for name in ("block", "seed", "traces", "spans-per", "n-res",
                 "attrs-per-span", "base-time-ns"):
        ap.add_argument("--" + name, type=int, required=True)
    build_block(ap.parse_args())
