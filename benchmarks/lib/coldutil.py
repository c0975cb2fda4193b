"""What the `*_cold` shapes share: which block a request reads, by a
schedule and not by a draw.

`shapeutil.draw_block` draws the block from the configuration's
`block_popularity` with `rnd.choices`, so the number of requests a window
sends to the blocks that are not staged follows the seed: of ~180 searches
at a quarter on the ten older blocks, 45 +- 6, and each of those is a miss of
seconds where a hit is a tenth of one. Here, inside each shape, the next
block is the one furthest behind its popularity among that shape's requests
so far, as `harness.shape_schedule` interleaves the shapes and
`rangeutil.draw_n` the ranges: every seed sends the same (shape, block) list
and two runs differ in operands, not in the work they drew. Weights, clients
and popularity are the mix's and the configuration's, untouched.

Blocks that are equally far behind -- the ten older ones, 2.5 % each -- are
taken in an order drawn from the shape's name, not from the seed and not by
index: with ties to the first, every shape walked the older blocks round
robin, the one access pattern in which an LRU never hits (the first build:
every old-block request missed the device AND the pool, and the cell ran a
third slower than under the plain draw, PERF.md section 6).
"""

from __future__ import annotations

import random


def next_block(env, shape: str) -> int:
    """Index of the block the shape's next request reads."""
    if env.force_block is not None:  # warm-up touches each block in turn
        return env.force_block
    pop = env.config["corpus"]["block_popularity"]
    indices = [b["index"] for b in env.blocks()]
    weights = (pop + [pop[-1]] * len(indices))[:len(indices)]
    counts, ties = env.used.setdefault(
        ("block_schedule", shape),
        ([0] * len(indices), random.Random(f"cold-schedule-{shape}")))
    i, total = sum(counts), sum(weights)
    behind = [round(weights[j] / total * (i + 1) - counts[j], 9)
              for j in range(len(indices))]
    k = ties.choice([j for j, d in enumerate(behind) if d == max(behind)])
    counts[k] += 1
    return indices[k]


def build_over(one, shape: str, rnd, env, params) -> dict:
    """`one.build` over the scheduled block: the one-block shape's own
    operands and window, its draw of the block replaced."""
    forced = env.force_block
    env.force_block = next_block(env, shape)
    try:
        return one.build(rnd, env, params)
    finally:
        env.force_block = forced
