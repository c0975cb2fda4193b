"""What the range shapes share: a request window over the newest N whole
blocks of an hourly blocklist, N by the time picker's presets.

Block 0 is the newest (lib/corpus.py dates block b at `top - (b + 1) *
(3600 s + gap_s)`), so "the last N hours" is blocks 0 .. N-1: `end` is the
newest block's last second and `start` lies in the gap before the N-th
newest block, up to `OFFSETS - 1` seconds before its first second. The
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


def draw_n(env, params, shape: str) -> int:
    """How many of the newest blocks the request covers: at every draw the
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
    return min(n, len(env.manifest["blocks"]))


def window(env, n: int, offset: int) -> dict:
    blocks = env.manifest["blocks"]
    start = blocks[n - 1]["start_s"] - offset
    if n < len(blocks) and start <= blocks[n]["end_s"]:
        raise ValueError(f"offset {offset} reaches block {n}: gap_s too small")
    return {"start": start, "end": blocks[0]["end_s"]}


def draw(rnd, env, params, shape: str, operands: int) -> tuple[int, int, dict]:
    """-> (N, an operand index below `operands`, the window): a pair of
    operand and offset this run has not drawn for (shape, N) before."""
    n = draw_n(env, params, shape)
    v = U.draw_unique(rnd, env, (shape, n), operands * OFFSETS)
    return n, v % operands, window(env, n, v // operands)


def traces_covered(env, op: dict) -> int:
    return sum(env.manifest["blocks"][b]["n_traces"]
               for b in U.blocks_overlapping(env, op["start"], op["end"]))
