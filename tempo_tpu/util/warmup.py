"""AOT warmup: pre-compile the known (op, shape-bucket) corpus at
startup (ROADMAP item 5's leftover).

kerneltel's record_launch notes every FIRST compile of an (op, bucket)
pair into the CostLedger (key `compile_corpus`) -- the durable record
of which program shapes this deployment actually serves. A process
started with `--warmup.shapes` replays that corpus through registered
warmup builders BEFORE serving: each builder compiles a canonical
program of that op at that bucket, which (a) populates the in-process
jit caches and (b) pulls the persistent XLA compilation cache
(JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) off disk ahead
of the first query, so the first-query p99 stops paying the compile
storm.

Builders are canonical, not exhaustive: the filter builder compiles a
single-predicate program per row bucket -- real queries with other
tree shapes still compile on first use, but the dominant storm (the
per-bucket base programs, and with the disk cache every previously
seen program) is paid before the listen socket opens.
"""

from __future__ import annotations

import threading
import time

from . import costledger

CORPUS_KEY = "compile_corpus"
CORPUS_MAX = 256  # distinct (op, bucket) pairs remembered

_lock = threading.Lock()
_seen: set[tuple[str, str]] = set()
_builders: dict[str, object] = {}


def register_builder(op: str, fn) -> None:
    """fn(bucket: int) compiles the canonical program of `op` at that
    row bucket (and blocks until ready)."""
    with _lock:
        _builders[op] = fn


def note_compile(op: str, bucket_label: str) -> None:
    """Record one first-compile into the ledger corpus (deduplicated,
    bounded, best-effort -- called from kerneltel.record_launch). The
    ledger read-modify-write stays under the module lock: two threads
    first-compiling different pairs concurrently would otherwise each
    publish a corpus missing the other's entry, and the in-process
    _seen gate would prevent the lost pair from ever being re-noted."""
    pair = (str(op), str(bucket_label))
    with _lock:
        if pair in _seen or len(_seen) >= CORPUS_MAX:
            return
        _seen.add(pair)
        led = costledger.ledger()
        ent = led.get(CORPUS_KEY) or {}
        pairs = {tuple(p) for p in ent.get("pairs", []) if len(p) == 2}
        if pair in pairs:
            return
        pairs.add(pair)
        led.update(CORPUS_KEY, pairs=sorted([list(p) for p in pairs]))
        led.publish()


def corpus() -> list[tuple[str, str]]:
    ent = costledger.ledger().get(CORPUS_KEY) or {}
    return [tuple(p) for p in ent.get("pairs", []) if len(p) == 2]


def reset_for_tests() -> None:
    with _lock:
        _seen.clear()


def _warm_filter(nb: int) -> None:
    """Canonical fused-filter program: one span predicate, all axes at
    the same bucket -- the base program every search compiles first."""
    import jax
    import numpy as np

    from ..ops.device import PAD_I32, pad_rows
    from ..ops.filter import Cond, Operands, T_SPAN, eval_block

    n = min(64, nb)
    cols = {
        "span.trace_sid": pad_rows(np.zeros(n, np.int32), nb, PAD_I32),
        "span.dur_us": pad_rows(np.arange(n, dtype=np.int32), nb, PAD_I32),
        "trace.span_off": pad_rows(np.asarray([0, n], np.int32), nb + 1,
                                   np.int32(n)),
    }
    conds = (Cond(target=T_SPAN, col="span.dur_us", op="ge"),)
    ops = Operands.build([(0, 10, 0, 0.0, 0.0)])
    jax.block_until_ready(
        eval_block((("cond", 0), conds), cols, ops, n, 1, nb, nb, nb))


register_builder("filter", _warm_filter)


def run_warmup() -> dict:
    """Compile the ledger corpus through the registered builders.
    Returns the report the app surfaces ({warmed, skipped, errors,
    wall_ms}); never raises -- a warmup failure must not stop serving."""
    t0 = time.perf_counter()
    with _lock:
        builders = dict(_builders)
    warmed: list[list[str]] = []
    skipped: list[list[str]] = []
    errors: list[str] = []
    for op, blab in corpus():
        fn = builders.get(op)
        if fn is None:
            skipped.append([op, blab])
            continue
        try:
            nb = int(blab)
        except ValueError:
            skipped.append([op, blab])
            continue
        try:
            fn(nb)
            warmed.append([op, blab])
        except Exception as e:  # noqa: BLE001 - warmup is best-effort
            errors.append(f"{op}@{blab}: {type(e).__name__}: {e}")
    return {
        "warmed": warmed,
        "skipped": skipped,
        "errors": errors,
        "wall_ms": round((time.perf_counter() - t0) * 1e3, 1),
    }
