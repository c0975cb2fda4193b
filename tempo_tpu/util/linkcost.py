"""Measured host<->device link cost, shared by every engine-choice
site (search's host-vs-staged decision, the generator's reduce).

One tiny put + compute + fetch is the fixed price of any device
launch: host execution wins for anything the host can scan faster than
that round trip, the device for everything larger. Measure once per
process, don't assume."""

from __future__ import annotations

import threading

import numpy as np

_LINK_RTT_MS: float | None = None
_rtt_lock = threading.Lock()


def link_rtt_ms() -> float:
    """One tiny put+compute+fetch round trip, measured at first use
    (first rep absorbs backend init + the +1 kernel compile). The lock
    keeps concurrent first callers from racing duplicate probes (and
    double-paying the backend-init rep)."""
    global _LINK_RTT_MS
    if _LINK_RTT_MS is None:
        with _rtt_lock:
            if _LINK_RTT_MS is None:
                import time as _time

                import jax.numpy as jnp

                # a backend that cannot start raises here: an RTT of 0
                # would route everything to a device that is not there
                probe = np.zeros(8, np.int32)
                best = float("inf")
                for _ in range(3):
                    t0 = _time.perf_counter()
                    np.asarray(jnp.asarray(probe) + 1)
                    best = min(best, _time.perf_counter() - t0)
                _LINK_RTT_MS = best * 1e3
    return _LINK_RTT_MS


def measured_link_rtt_ms() -> float | None:
    """The probe's result if some engine choice already took it, else
    None -- for status payloads, which must not launch a probe."""
    return _LINK_RTT_MS
