"""Shape bucketing and host->device column staging.

XLA compiles one program per input-shape signature; trace blocks all have
different row counts. Padding every axis to a power-of-two bucket keeps
the number of distinct compiled programs logarithmic in block size
(SURVEY.md 7.3 "recompilation"). Pad rows carry sentinels that can never
match a predicate and never land in a real segment.
"""

from __future__ import annotations

import numpy as np

# persistent XLA compilation cache: enabled at import of THE module
# every kernel imports, so it covers the first compile of any entry
# point (app, CLI, bench, tests) -- in JAX_COMPILATION_CACHE_DIR when
# set, else <checkout>/.jax_cache. A no-op once the app enabled it.
from ..util.costmodel import enable_default_compile_cache

enable_default_compile_cache()

MIN_BUCKET = 1024
PAD_I32 = np.int32(-(2**31))  # sentinel for code/int columns (never a valid code)


def launch_tap(op: str) -> None:
    """Chaos launch shim: every device-kernel launch passes here (via
    TEL.record_launch, the one chokepoint all entry points share) so a
    chaos rule on site `device.launch` can simulate an XLA compile
    failure, a device OOM (RESOURCE_EXHAUSTED), or a slow launch --
    keyed by op name. Only called when a fault plane is active; with
    chaos off the kerneltel fast path never reaches this module."""
    from ..chaos import plane as chaos_plane

    chaos_plane.tap("device.launch", key=str(op))


def scoped(op: str):
    """Decorator for a jitted body: its whole trace runs inside
    jax.named_scope("tempo.<op>") (op = the kernel's record_launch
    name), so a device trace's XLA ops say which kernel they belong to.
    The function keeps its own name: the module stays `jit_run` /
    `jit_sel` / ... for whatever groups by module name, and the scope
    is HLO metadata only -- persistent-cache keys do not move."""
    import functools

    import jax

    def deco(f):
        @functools.wraps(f)
        def inner(*args, **kwargs):
            with jax.named_scope("tempo." + op):
                return f(*args, **kwargs)
        return inner
    return deco


def bucket(n: int) -> int:
    """Next power-of-two >= max(n, MIN_BUCKET)."""
    b = MIN_BUCKET
    while b < n:
        b <<= 1
    return b


def pad_rows(arr: np.ndarray, n: int, fill) -> np.ndarray:
    """Pad axis 0 to n rows with `fill` (`arr` itself when it has n):
    one allocation, the rows copied once, the tail filled once."""
    rows = arr.shape[0]
    if rows == n:
        return arr
    out = np.empty((n,) + arr.shape[1:], dtype=arr.dtype)
    out[:rows] = arr
    out[rows:] = fill
    return out


def pad_columns(
    cols: dict[str, np.ndarray],
    n: int,
    fills: dict[str, object] | None = None,
    default_fill=PAD_I32,
) -> dict[str, np.ndarray]:
    fills = fills or {}
    return {k: pad_rows(v, n, fills.get(k, default_fill)) for k, v in cols.items()}
