"""What the range shapes share: a request window over the newest N whole
hours of an hourly blocklist, N by the time picker's presets.

Window 0 is the newest (lib/corpus.py dates window w at `top - (w + 1) *
(3600 s + gap_s)`; one block a window unless the configuration's
`blocks_per_window` says more), so "the last N hours" is windows 0 .. N-1
with every block in them: `end` is the newest window's last second and
`start` lies in the gap before the N-th newest window, up to `OFFSETS - 1`
seconds before its first second. The
offset selects no other block (neighbours are gap_s - 1 = 179 s apart) and
moves no program's shape (a one-block `rate()` at step 60 s stays within 64
buckets: 3,602 + 170 + 59 < 3,840 s); it makes the frontend's result cache,
which keys on the query and the exact `start` / `end`, answer nothing where
a shape has few operands (64 services x 5 ranges for ~1,000 searches a
window).
"""

from __future__ import annotations

from . import shapeutil as U

BLOCKS = (1, 3, 6, 12, 24)  # Grafana's "Last 1 / 3 / 6 / 12 / 24 hours"
WEIGHTS = (0.35, 0.25, 0.20, 0.12, 0.08)
OFFSETS = 171


def hours(env, tenant=None) -> list[list[dict]]:
    """One tenant's blocks (`Env.blocks`) by compaction window, newest
    first. A manifest from before blocks could share a window has one block
    an hour, in index order."""
    by_window: dict = {}
    for b in env.blocks(tenant):
        by_window.setdefault(b.get("window", b["index"]), []).append(b)
    return [by_window[w] for w in sorted(by_window)]


def draw_n(env, params, shape: str) -> int:
    """How many of the newest hours the request covers: at every draw the
    N furthest behind its weight among this shape's requests so far (ties
    to the first), as harness.shape_schedule interleaves the shapes -- the
    same (shape, N) list for every seed, so two runs differ in operands and
    not in the work they drew (a tag search over 24 blocks costs 50 x one
    over 1). Warm-up's `per_block` step forces a block index: it maps onto
    the list of N, so every (shape, N) pair is sent before the window."""
    ns = params.get("blocks", BLOCKS)
    if env.force_block is not None:
        n = ns[env.force_block % len(ns)]
    else:
        weights = params.get("weights", WEIGHTS)
        counts = env.used.setdefault(("n_schedule", shape), [0] * len(ns))
        i, total = sum(counts), sum(weights)
        k = max(range(len(ns)),
                key=lambda j: (weights[j] / total * (i + 1) - counts[j], -j))
        counts[k] += 1
        n = ns[k]
    return min(n, len(hours(env, params.get("tenant"))))


def window(env, n: int, offset: int, tenant=None) -> dict:
    hs = hours(env, tenant)
    start = min(b["start_s"] for b in hs[n - 1]) - offset
    if n < len(hs) and start <= max(b["end_s"] for b in hs[n]):
        raise ValueError(f"offset {offset} reaches hour {n}: gap_s too small")
    return {"start": start, "end": max(b["end_s"] for b in hs[0])}


def draw(rnd, env, params, shape: str, operands: int) -> tuple[int, int, dict]:
    """-> (N, an operand index below `operands`, the window): a pair of
    operand and offset this run has not drawn for (shape, N) before."""
    n = draw_n(env, params, shape)
    v = U.draw_unique(rnd, env, (shape, n), operands * OFFSETS)
    return n, v % operands, window(env, n, v // operands, params.get("tenant"))


def traces_covered(env, op: dict) -> int:
    return sum(env.manifest["blocks"][b]["n_traces"]
               for b in U.blocks_overlapping(env, op["start"], op["end"],
                                             op.get("tenant")))
