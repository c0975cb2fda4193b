"""What the request shapes share: drawing a block, a block's own time
window, and reading a search answer."""

from __future__ import annotations

import json
import urllib.parse


def draw_block(rnd, env, tenant=None) -> int:
    """Index of a block of one tenant (`Env.blocks`: the first, where none
    is named), by the configuration's popularity (newest first)."""
    if env.force_block is not None:  # warm-up touches each block in turn
        return env.force_block
    pop = env.config["corpus"]["block_popularity"]
    indices = [b["index"] for b in env.blocks(tenant)]
    n = len(indices)
    weights = (pop + [pop[-1]] * n)[:n]
    return rnd.choices(indices, weights=weights)[0]


def draw_unique(rnd, env, key, n: int) -> int:
    """A number below n that this run has not drawn for `key` before, so that
    no request repeats another and the result cache answers nothing. Once
    every value is used the set starts again (a list far longer than any
    window consumes)."""
    used = env.used.setdefault(key, set())
    if len(used) >= n:
        used.clear()
    while True:
        v = rnd.randrange(n)
        if v not in used:
            used.add(v)
            return v


def window(env, block: int) -> dict:
    """start/end (unix seconds) that select this block whole and nothing
    beside its compaction window: the first and last second of the blocks
    that share the window with it, no padding. With one block a window
    (the default) that is the block's own first and last second and selects
    exactly it; where `blocks_per_window` puts several there, every one of
    them is covered whole and `union` answers for all."""
    b = env.manifest["blocks"][block]
    mates = [m for m in env.blocks(b.get("tenant"))
             if m.get("window", m["index"]) == b.get("window", b["index"])]
    return {"start": min(m["start_s"] for m in mates),
            "end": max(m["end_s"] for m in mates)}


def blocks_overlapping(env, start: int, end: int, tenant=None) -> list[int]:
    """Indices of one tenant's blocks (`Env.blocks`) the window overlaps."""
    return [b["index"] for b in env.blocks(tenant)
            if b["start_s"] <= end and b["end_s"] >= start]


def get(path: str, params: dict):
    return "GET", path + "?" + urllib.parse.urlencode(params), None, {}


def search_ids(status: int, body: bytes):
    """-> (list of 32-hex trace ids, '') or (None, why)."""
    if status != 200:
        return None, f"HTTP {status}: {body[:200]!r}"
    try:
        out = json.loads(body)
        return [t["traceID"].rjust(32, "0") for t in out["traces"]], ""
    except (ValueError, KeyError, TypeError) as e:
        return None, f"unreadable answer: {e}"


def union(env, start: int, end: int, fn, tenant=None) -> set:
    """The oracle's answer over every block of the tenant that the window
    overlaps."""
    out: set = set()
    for b in blocks_overlapping(env, start, end, tenant):
        out |= fn(env.oracle(b))
    return out


def equal_sets(got, want) -> tuple[bool, str]:
    got, want = set(got), set(want)
    if got == want:
        return True, ""
    return False, (f"got {len(got)} want {len(want)}; missing "
                   f"{sorted(want - got)[:2]} extra {sorted(got - want)[:2]}")
