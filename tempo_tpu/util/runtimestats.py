"""Runtime health gauges: the Go-runtime metrics the reference gets
for free (goroutines, GC pauses, RSS), for a CPython process.

  tempo_runtime_gc_collections_total{generation}  via gc.callbacks
  tempo_runtime_gc_pause_seconds{generation}      stop-the-world pause
  tempo_runtime_threads                           live thread count
  tempo_runtime_rss_bytes                         resident set size
  tempo_runtime_open_fds                          open file descriptors
  tempo_runtime_cpu_seconds_total                 CPU of the whole process
  tempo_runtime_gil_wait_seconds                  the sampler's lateness

The last two are the interpreter as a measured layer (`interp` of
/status/kernels, interp_stats): how busy the process is, and how long a
thread that becomes runnable waits to run. The always-on sampler
(util/profiler) waits one period and then needs the GIL back to take its
sample; how late it gets it is the wait every thread pays after a device
wait, a read or a lock. It also holds what the OS adds to a wake-up
(tens of microseconds on an idle host) and a C call that keeps the GIL
past the switch interval reads as one long wait.

Counters accumulate from the moment install() first runs (the app
installs at start; the /metrics chokepoint installs lazily as a
belt-and-braces). Point-in-time gauges refresh at scrape.
"""

from __future__ import annotations

import gc
import os
import threading
import time

from .metrics import Counter, Gauge, Histogram

# CPython gen-0 sweeps run sub-ms; a gen-2 pass over a large heap can
# stall tens of ms -- exactly the tail-latency blip worth a bucket edge
GC_PAUSE_BUCKETS = (1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.025,
                    0.05, 0.1, 0.25, 1.0)

GC_COLLECTIONS = Counter(
    "tempo_runtime_gc_collections_total",
    help="CPython garbage collections by generation")
GC_PAUSE = Histogram(
    "tempo_runtime_gc_pause_seconds", buckets=GC_PAUSE_BUCKETS,
    help="CPython GC stop-the-world pause by generation")
THREADS = Gauge("tempo_runtime_threads",
                help="live Python threads (the goroutine-count analog)")
RSS = Gauge("tempo_runtime_rss_bytes",
            help="resident set size of this process")
OPEN_FDS = Gauge("tempo_runtime_open_fds",
                 help="open file descriptors of this process")
CPU = Counter(
    "tempo_runtime_cpu_seconds_total",
    help="CPU seconds of this process, every thread (time.process_time)")
# edges at one switch interval (5 ms: a wait behind one other thread)
# and at four (behind every worker of a busy process)
GIL_WAIT_BUCKETS = (0.001, 0.005, 0.02, 0.1, 0.5)
GIL_WAIT = Histogram(
    "tempo_runtime_gil_wait_seconds", buckets=GIL_WAIT_BUCKETS,
    help="how late the always-on sampler got the interpreter back "
         "after each period: the wait of a thread that becomes runnable")

_T0 = time.monotonic()

_install_lock = threading.Lock()
_installed = False
_gc_lock = threading.Lock()
_gc_t0: dict[int, float] = {}  # generation -> collection start


def _gc_cb(phase: str, info: dict) -> None:
    try:
        gen = int(info.get("generation", 0))
        if phase == "start":
            with _gc_lock:
                _gc_t0[gen] = time.perf_counter()
            return
        with _gc_lock:
            t0 = _gc_t0.pop(gen, None)
        GC_COLLECTIONS.inc(labels=f'generation="{gen}"')
        if t0 is not None:
            GC_PAUSE.observe(time.perf_counter() - t0,
                             f'generation="{gen}"')
    except Exception:
        pass  # a GC callback must never raise into the collector


def install() -> None:
    """Register the GC callback once per process."""
    global _installed
    with _install_lock:
        if _installed:
            return
        gc.callbacks.append(_gc_cb)
        _installed = True


def _rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        try:
            import resource

            # ru_maxrss is KiB on Linux: peak, not current -- still a
            # usable ceiling where /proc is absent
            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            return 0


def _open_fds() -> int:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return 0


def probe_stats() -> dict:
    """The sampler's lateness probe, read off GIL_WAIT (one counter, two
    views); all 0 with the sampler off."""
    counts, late, ticks = GIL_WAIT.snapshot().get(
        "", ([0] * (len(GIL_WAIT_BUCKETS) + 1), 0.0, 0))
    return {
        "ticks": ticks,
        "late_seconds": round(late, 6),
        "late_over_5ms": sum(counts[GIL_WAIT_BUCKETS.index(0.005) + 1:]),
        "late_over_20ms": sum(counts[GIL_WAIT_BUCKETS.index(0.02) + 1:]),
    }


def interp_stats() -> dict:
    """The `interp` section of /status/kernels. Every field adds up over
    the instances of a tree."""
    return {
        "cpu_seconds": round(time.process_time(), 6),
        "wall_seconds": round(time.monotonic() - _T0, 6),
        "probe": probe_stats(),
    }


def refresh() -> None:
    with _install_lock:  # two scrapes at once must not add one delta twice
        CPU.inc(time.process_time() - CPU.get())
    THREADS.set(threading.active_count())
    RSS.set(_rss_bytes())
    OPEN_FDS.set(_open_fds())


def metrics_lines() -> list[str]:
    install()  # lazy belt-and-braces: scrape implies counting
    refresh()
    return (GC_COLLECTIONS.text() + GC_PAUSE.text() + THREADS.text()
            + RSS.text() + OPEN_FDS.text() + CPU.text() + GIL_WAIT.text())


def help_entries() -> dict[str, str]:
    return {
        "tempo_runtime_gc_collections": GC_COLLECTIONS.help,
        "tempo_runtime_gc_pause_seconds": GC_PAUSE.help,
        "tempo_runtime_threads": THREADS.help,
        "tempo_runtime_rss_bytes": RSS.help,
        "tempo_runtime_open_fds": OPEN_FDS.help,
        "tempo_runtime_cpu_seconds": CPU.help,
        "tempo_runtime_gil_wait_seconds": GIL_WAIT.help,
    }
