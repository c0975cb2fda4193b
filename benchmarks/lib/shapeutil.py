"""What the request shapes share: drawing a block, a block's own time
window, and reading a search answer."""

from __future__ import annotations

import json
import urllib.parse


def draw_block(rnd, env) -> int:
    """Index of a block, by the configuration's popularity (newest first)."""
    if env.force_block is not None:  # warm-up touches each block in turn
        return env.force_block
    pop = env.config["corpus"]["block_popularity"]
    n = len(env.manifest["blocks"])
    weights = (pop + [pop[-1]] * n)[:n]
    return rnd.choices(range(n), weights=weights)[0]


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
    """start/end (unix seconds) that select exactly this block: its own
    first and last second, no padding."""
    b = env.manifest["blocks"][block]
    return {"start": b["start_s"], "end": b["end_s"]}


def blocks_overlapping(env, start: int, end: int) -> list[int]:
    return [b["index"] for b in env.manifest["blocks"]
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


def union(env, start: int, end: int, fn) -> set:
    """The oracle's answer over every block the window overlaps."""
    out: set = set()
    for b in blocks_overlapping(env, start, end):
        out |= fn(env.oracle(b))
    return out


def equal_sets(got, want) -> tuple[bool, str]:
    got, want = set(got), set(want)
    if got == want:
        return True, ""
    return False, (f"got {len(got)} want {len(want)}; missing "
                   f"{sorted(want - got)[:2]} extra {sorted(got - want)[:2]}")
