"""Device mesh construction for the query/compaction axes."""

from __future__ import annotations

import threading

import jax
from jax.sharding import Mesh

# Multi-device (collective) programs dispatched concurrently from
# several host threads can interleave their per-device enqueue order --
# thread A lands program1 on device 0 first while thread B lands
# program2 on device 3 first -- and the collectives then wait on each
# other forever (observed as a hard hang in test_stress's concurrent
# searchers on the 8-device CPU mesh; the same cross-ordering hazard
# exists on real chips). Every mesh host entry point serializes its
# dispatch+fetch under this lock; single-device kernels are unaffected.
DISPATCH_LOCK = threading.Lock()


def smap(f, mesh, in_specs, out_specs):
    """shard_map with the varying-axes check off: our kernels mix
    replicated operands (queries, predicate operands) with device-varying
    shards inside fori_loops, which the strict vma check rejects."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _factor(n: int) -> tuple[int, int]:
    """(dp, sp) with dp*sp == n, dp the largest divisor <= sqrt(n)."""
    dp = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            dp = d
        d += 1
    return dp, n // dp


def make_mesh(n_devices: int | None = None, dp: int | None = None, sp: int | None = None) -> Mesh:
    """2D mesh with axes ('dp', 'sp'): dp shards blocks, sp shards rows
    within a block. Defaults to all visible devices, near-square split so
    both axes are exercised (8 devices -> 2x4)."""
    devices = jax.devices()
    n = n_devices or len(devices)
    if dp is None and sp is None:
        dp, sp = _factor(n)
    elif dp is None:
        dp = n // sp
    elif sp is None:
        sp = n // dp
    assert dp * sp == n, f"dp*sp ({dp}*{sp}) != n_devices ({n})"
    import numpy as np

    return Mesh(np.asarray(devices[:n]).reshape(dp, sp), ("dp", "sp"))
