"""Kernel telemetry: device-execution observability for the read path.

The HTTP layer says how long a query took; this subsystem says WHY --
recompile storm, host fallback, padding waste, or transfer stall. One
process-wide registry (TEL) collects, from every device entry point in
ops/ and parallel/:

  * compile vs jit-cache-hit counters keyed by (op, shape-bucket): the
    ops pad every axis to a power-of-two bucket (ops/device.bucket), so
    the (op, bucket-signature) pair IS the XLA program key. The model
    tracks OUR cache key, not XLA's internals, so an lru_cache eviction
    that forces a silent re-trace undercounts -- acceptable for an
    operational signal (evictions mean 256+ live program shapes).
  * per-op device wall-time histograms. When sync timing is on the
    observer calls block_until_ready, so the histogram records true
    device time rather than Python dispatch; the sync costs a link
    round trip per kernel, so the default follows the measured link
    (util/linkcost): sync when RTT <= SYNC_RTT_MS,
    dispatch-only otherwise. TEMPO_KERNELTEL_SYNC=0|1 overrides.
  * host->device transfer bytes + padding-waste rows per staging call
    (ops/stage), plus staged-cache hit/miss counters.
  * engine routing decisions WITH reasons (cold block, pre-IO budget
    exceeded, lossy/unplannable plan, mesh fallback, ...) from
    db/search, db/metrics_exec and db/metrics_mesh.
  * a bounded recent-query log (slowest first in /status/kernels), each
    entry carrying its self-trace id so a slow query links straight to
    its flame view.

One span primitive, three sinks: `with TEL.stage("<layer>:<stage>",
**attrs):` times its body into the cumulative `stages` table of
/status/kernels (always on), records a child span on the self-trace the
frontend parked in a contextvar (set_active_trace) and is the ambient
parent of its body, and wraps it in a jax.profiler.TraceAnnotation
("tempo/<name>", inert without a profiler session) so a device trace's
idle gaps are owned by layers. A stage is per block or per request,
never per row. It reads two clocks, the wall's and its own thread's CPU
(a row is {count, seconds, cpu_seconds}): a wall-clock stage in a
contended interpreter measures the contention, the CPU clock the work.
A job's whole run is the stage `run:<kind>` (services/frontend,
services/worker); /status/kernels `interp` holds the process's CPU and
the sampler's lateness probe (util/runtimestats). child_span() stays
for retroactive leaves (`verify`, queue-wait, the result-cache hits).
Everything here is advisory -- no method may raise into the query path.
"""

from __future__ import annotations

import contextvars
import os
import sys
import threading
import time
from collections import OrderedDict, deque

from ..chaos import plane as _chaos
from .metrics import Counter, Gauge, Histogram
from .runtimestats import interp_stats

# device kernels run sub-ms to ~seconds: a finer low end than the
# request-latency default buckets
DEVICE_TIME_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                       0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

# compaction pipeline stages span sub-ms (tiny-block fetch) to tens of
# seconds (a big level-1 merge)
COMPACT_STAGE_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                         0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0)

QUERY_LOG_SIZE = 64  # recent queries kept for the slow-query log
SYNC_RTT_MS = 2.0  # block_until_ready timing only below this link RTT
# bound on remembered compile signatures: full query structures key the
# set, so an unbounded set would grow forever in a long-lived querier.
# LRU eviction mirrors what the jitted functions' lru_caches do anyway
# (an evicted program recompiles on next use, and we count it again).
SEEN_SIGNATURES_MAX = 4096

_active_trace: contextvars.ContextVar = contextvars.ContextVar(
    "tempo_selftrace", default=None)

# placement the current job was dequeued under (own/steal/unowned, "" =
# no affinity context): the frontend/worker parks it around execution so
# ops/stage can attribute staged-cache hits to owner-vs-stolen routing
_affinity_placement: contextvars.ContextVar = contextvars.ContextVar(
    "tempo_affinity_placement", default="")

# A thread's CPU clock is a system call: 0.3 us on a plain Linux host,
# 5.8 us on the chip's (where it also ticks in 10 ms steps), and stage
# boundaries come in clusters -- a parent and its first child open within
# microseconds, a last child and its parent close together. A read taken
# on the same thread less than this long ago stands in for a new one, so
# a stage's cpu_seconds is exact to within it at either end.
CPU_REUSE_S = 50e-6
_cpu_tls = threading.local()


def _thread_cpu(now: float) -> float:
    """This thread's CPU seconds at perf_counter() `now`."""
    tl = _cpu_tls
    try:
        if now - tl.at < CPU_REUSE_S:
            return tl.cpu
    except AttributeError:  # this thread's first read
        pass
    tl.cpu = cpu = time.thread_time()
    tl.at = now
    return cpu


QOS_SHED_TENANTS_MAX = 128  # per-tenant shed rows kept before _overflow


def _esc_label(v: str) -> str:
    """Prometheus label-value escaping; delegates to the shared
    util/metrics.escape_label (kept as a module-local name because the
    call sites predate the public helper)."""
    from .metrics import escape_label

    return escape_label(v)


class _Stage:
    """One timed stage (TEL.stage): counter + self-trace span +
    profiler annotation. `attrs` may be filled in the body; the span
    records them as they are on exit, the annotation as on entry.
    `seconds` holds the duration after exit and `cpu_seconds` what of it
    this thread spent on a CPU (time.thread_time: the rest it was off
    the CPU -- waiting for the GIL, a lock, I/O, the device or the pool
    threads it handed work to, whose CPU is in their own stages' rows);
    the span carries it as `cpu_ms`. A body that finds it did none of
    the stage's work sets `counted = False` and the table and its
    histogram get no sample."""

    __slots__ = ("tel", "name", "attrs", "t0", "c0", "seconds", "cpu_seconds",
                 "counted", "_span", "_ann")

    def __init__(self, tel, name: str, attrs: dict):
        self.tel, self.name, self.attrs = tel, name, attrs
        self.seconds, self.cpu_seconds, self.counted = 0.0, 0.0, True

    def __enter__(self):
        self._span = self._ann = None
        try:
            t = _active_trace.get()
            if t is not None:
                self._span = t.span(self.name, self.attrs)
                self._span.__enter__()
            # only once THIS process runs jax (a session needs it anyway):
            # control-plane processes keep stages at their clock reads
            prof = sys.modules.get("jax.profiler")
            if prof is not None:
                self._ann = prof.TraceAnnotation("tempo/" + self.name, **self.attrs)
                self._ann.__enter__()
        except Exception:
            pass  # observability must never fail the body
        self.c0 = _thread_cpu(time.perf_counter())
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self.seconds = t1 - self.t0
        self.cpu_seconds = _thread_cpu(t1) - self.c0
        try:
            if self._ann is not None:
                self._ann.__exit__(exc_type, exc, tb)
            if self._span is not None:
                self._span.attrs["cpu_ms"] = round(self.cpu_seconds * 1e3, 3)
                self._span.__exit__(exc_type, exc, tb)
            if self.counted:
                self.tel._add_stage(self.name, self.seconds, self.cpu_seconds)
        except Exception:
            pass
        return False


class _Launch(_Stage):
    """One kernel dispatch (TEL.launch): a `kernel:launch` stage whose
    exit also closes the op's device-time window (observe_device)."""

    __slots__ = ()

    def sync(self, out):
        """Wait for `out` inside the window when sync timing is on, so
        it covers device execution, not just the dispatch."""
        try:
            if self.tel.sync_timing():
                import jax

                jax.block_until_ready(out)
        except Exception:
            pass
        return out

    def __exit__(self, exc_type, exc, tb):
        _Stage.__exit__(self, exc_type, exc, tb)
        if exc_type is None:
            self.tel.observe_device(self.attrs["op"], self.attrs["bucket"], self.t0)
        return False


class KernelTelemetry:
    def __init__(self):
        self._lock = threading.Lock()
        # TEL.stage: name -> [count, seconds, histogram | None, labels,
        # cpu seconds | None (record_stage rows have no thread to ask)]
        self._stages: dict[str, list] = {}
        self._stages_at_session: dict | None = None  # mark_session
        self._tls = threading.local()
        self._sync: bool | None = None
        self.compiles = Counter(
            "tempo_kernel_compiles_total",
            help="XLA program compiles by op and shape bucket")
        self.cache_hits = Counter(
            "tempo_kernel_cache_hits_total",
            help="jit-cache hits by op and shape bucket")
        self.device_time = Histogram(
            "tempo_kernel_device_seconds", buckets=DEVICE_TIME_BUCKETS,
            help="per-op device wall time (block_until_ready when the "
                 "link is fast; dispatch time otherwise)")
        self.transfer_bytes = Counter(
            "tempo_stage_transfer_bytes_total",
            help="host->device bytes uploaded by block staging")
        self.staged_rows_real = Counter(
            "tempo_stage_rows_real_total",
            help="real (pre-padding) rows staged to device")
        self.staged_rows_padded = Counter(
            "tempo_stage_rows_padded_total",
            help="rows staged to device after bucket padding")
        self.staged_cache_hits = Counter(
            "tempo_stage_cache_hits_total",
            help="staged-column device cache hits")
        self.staged_cache_misses = Counter(
            "tempo_stage_cache_misses_total",
            help="staged-column device cache misses (uploads)")
        self.staged_cache_evictions = Counter(
            "tempo_stage_cache_evictions_total",
            help="staged columns the device cache's LRU evicted to stay "
                 "under its byte budget")
        self.staged_cache_evicted_bytes = Counter(
            "tempo_stage_cache_evicted_bytes_total",
            help="device bytes of the staged columns the LRU evicted")
        self.staged_column_hits = Counter(
            "tempo_stage_column_hits_total",
            help="columns a staged-cache lookup found resident on the "
                 "device")
        self.staged_column_misses = Counter(
            "tempo_stage_column_misses_total",
            help="columns a staged-cache lookup had to stage (host pool "
                 "or backend read, assemble, upload)")
        self.staged_bytes_reused = Counter(
            "tempo_stage_bytes_reused_total",
            help="device bytes of resident columns that staged-cache "
                 "lookups did not have to stage again")
        self.routing = Counter(
            "tempo_engine_routing_total",
            help="engine routing decisions by layer, engine and reason")
        # cross-query batching executor (db/batchexec): fused launches
        self.batch_groups = Counter(
            "tempo_batch_groups_total",
            help="fused batch launches by executor")
        self.batch_queries = Counter(
            "tempo_batch_queries_total",
            help="queries admitted into the batching executor")
        self.batch_occupancy = Histogram(
            "tempo_batch_occupancy_queries",
            buckets=(1, 2, 4, 8, 16, 32, 64),
            help="queries coalesced per fused launch group")
        self.batch_window_wait = Histogram(
            "tempo_batch_window_wait_seconds",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1),
            help="admission-window wait paid by each batch leader")
        self.batch_demux = Counter(
            "tempo_batch_demux_total",
            help="per-query results demultiplexed out of fused launches")
        self._batches: dict[str, dict] = {}
        # mesh-batched serving (parallel/multiquery): one admission
        # window lowered to a single Q-programs x sharded-rows launch
        # across every chip -- launches and per-launch occupancy
        self.mesh_batch_launches = Counter(
            "tempo_mesh_batch_launches_total",
            help="batched multi-query mesh launches (one admission "
                 "window -> all chips)")
        self.mesh_batch_queries = Counter(
            "tempo_mesh_batch_queries_total",
            help="queries fused into batched mesh launches")
        self.mesh_batch_occupancy = Histogram(
            "tempo_mesh_batch_occupancy_queries",
            buckets=(1, 2, 4, 8, 16, 32, 64),
            help="queries per batched mesh launch")
        self._mesh_batches: dict = {"launches": 0, "queries": 0,
                                    "max_occupancy": 0}
        # compaction pipeline (db/compact_pipeline): per-stage wall
        # times, admission-gate occupancy, prefetch effectiveness
        self.compact_stage_time = Histogram(
            "tempo_compaction_stage_seconds", buckets=COMPACT_STAGE_BUCKETS,
            help="per-stage wall time of compaction pipeline jobs")
        self.compact_jobs = Counter(
            "tempo_compaction_jobs_total",
            help="compaction jobs executed by the pipeline by outcome")
        self.compact_input_bytes = Counter(
            "tempo_compaction_input_bytes_total",
            help="compaction input bytes consumed by completed jobs")
        self.compact_prefetch = Counter(
            "tempo_compaction_prefetch_total",
            help="pipeline input-prefetch outcomes by kind (hit/miss/waste)")
        self.compact_jobs_inflight = Gauge(
            "tempo_compaction_jobs_inflight",
            help="compaction jobs currently admitted into the pipeline")
        self.compact_bytes_inflight = Gauge(
            "tempo_compaction_bytes_inflight",
            help="estimated peak host-RAM bytes of admitted compaction jobs")
        self.compact_queue_depth = Gauge(
            "tempo_compaction_queue_depth",
            help="compaction jobs waiting at the pipeline admission gate")
        self._compaction: dict = {
            "runs": 0, "wall_seconds": 0.0, "stage_seconds": {},
            "jobs": 0, "errors": 0, "input_bytes": 0,
            "prefetch": {"hit": 0, "miss": 0, "waste": 0},
            "max_jobs_inflight": 0,  # process lifetime
            "run_max_jobs_inflight": 0,  # current/most-recent pipeline run
        }
        self.compact_passthrough_bytes = Counter(
            "tempo_compaction_passthrough_bytes_total",
            help="compressed bytes compaction copied through verbatim "
                 "(chunk passthrough + concat part copies) instead of "
                 "decompress->recompress")
        # cold-read streaming pipeline (ops/stream): per-stage wall
        # times, admission-gate bytes, unit outcomes
        self.stream_stage_time = Histogram(
            "tempo_stream_stage_seconds", buckets=COMPACT_STAGE_BUCKETS,
            help="per-stage wall time of cold-read stream pipeline units "
                 "(fetch/decompress/assemble/upload)")
        self.stream_units = Counter(
            "tempo_stream_units_total",
            help="cold-read stream pipeline units completed by outcome")
        self.stream_bytes_inflight = Gauge(
            "tempo_stream_bytes_inflight",
            help="estimated host bytes of admitted stream pipeline units")
        self._stream: dict = {
            "runs": 0, "wall_seconds": 0.0,
            "units": 0, "errors": 0, "cancelled": 0,
        }
        # cache-affinity scheduling (services/frontend): dequeue
        # placement outcomes, per-tenant QoS sheds, and staged-cache
        # lookups attributed by the dequeue placement of the job that
        # made them (owner-vs-stolen hit-rate attribution)
        self.affinity_jobs = Counter(
            "tempo_affinity_jobs_total",
            help="frontend dequeue placement outcomes (own/steal/unowned)")
        self.affinity_warm_steals = Counter(
            "tempo_affinity_warm_steals_total",
            help="steals taken before the steal timeout by a cache "
                 "domain that reported the job's block as staged")
        self.qos_shed = Counter(
            "tempo_qos_shed_total",
            help="queries shed with 429 by per-tenant read QoS budgets")
        self.staged_placement = Counter(
            "tempo_stage_cache_placement_total",
            help="staged-cache lookups by job placement (own/steal/"
                 "unowned/none) and result")
        self._affinity: dict[str, int] = {}
        self._affinity_warm_steals = 0
        # job dispatch (services/frontend): jobs by where they ran, per
        # worker [jobs, busy seconds], bytes over the frontend -> querier wire
        self._dispatch_jobs: dict[str, int] = {"local": 0, "remote": 0}
        self._dispatch_workers: dict[str, list] = {}
        self._dispatch_wire_bytes = 0
        # requests over the blocklist: how many, by blocks covered, and
        # the block jobs built for them (record_range)
        self._range_searches: dict[int, int] = {}
        self._range_jobs = 0
        self._range_job_blocks = 0
        self._qos_sheds: dict[str, dict[str, int]] = {}
        self._staged_by_placement: dict[str, list[int]] = {}
        # live-head staging (ops/livestage): slot/row occupancy by
        # lifecycle state, delta-upload volume, push->device-visible lag
        self.livestage_rows = Gauge(
            "tempo_livestage_rows",
            help="live-head staged slots by lifecycle state "
                 "(live/cut/flushing/dead) and membership rows (rows)")
        self.livestage_delta_bytes = Counter(
            "tempo_livestage_delta_bytes_total",
            help="host->device bytes uploaded by live-head staging "
                 "refreshes (delta appends + slot columns)")
        self.livestage_lag = Histogram(
            "tempo_livestage_lag_seconds",
            buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
            help="staging lag: push acknowledged -> segment visible to "
                 "the device live engine")
        self._livestage: dict = {
            "slots": {}, "rows": 0, "generation": 0,
            "uploads": 0, "full_uploads": 0, "delta_bytes": 0,
            "delta_rows": 0, "lag_count": 0, "lag_sum": 0.0, "lag_max": 0.0,
        }
        # device-native ingest (tempo_tpu/ingest): per-stage write-path
        # seconds (decode / wal_append / lock_wait / stage_delta / swap /
        # cut / flush),
        # window/feature-checkpoint volume, replay outcomes
        self.ingest_stage_time = Histogram(
            "tempo_ingest_stage_seconds",
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
            help="write-path stage wall seconds by stage "
                 "(decode/wal_append/lock_wait/stage_delta/swap/cut/flush)")
        self._ingest: dict = {
            "windows": 0, "window_traces": 0,
            "window_bytes": 0, "feature_entries": 0,
            "replays": {"records": 0, "features": 0, "torn": 0},
        }
        # streaming metrics-generator (services/generator): per-stage
        # fold seconds, push->series-visible freshness, per-tenant
        # series-limit sheds, window/pairing volume
        self.generator_stage_time = Histogram(
            "tempo_generator_stage_seconds",
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
            help="streaming generator fold wall seconds by stage "
                 "(span-metrics/service-graphs)")
        self.generator_freshness = Histogram(
            "tempo_generator_freshness_seconds",
            buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
            help="push received -> generated series visible to the "
                 "next exposition scrape")
        self.generator_shed = Counter(
            "tempo_generator_series_shed_total",
            help="spans shed by the per-tenant max-active-series limit")
        self._generator: dict = {
            "windows": 0, "window_spans": 0,
            "edges_completed": 0, "unpaired": 0, "expired": 0,
            "shed": {}, "freshness_count": 0, "freshness_sum": 0.0,
            "freshness_max": 0.0,
        }
        # self-tracing pipeline health (services/selftrace): spans
        # shipped vs whole traces dropped at the bounded in-flight queue
        self.selftrace_spans = Counter(
            "tempo_selftrace_spans_total",
            help="self-trace spans by outcome (shipped / dropped with "
                 "their trace at the bounded in-flight queue)")
        self._selftrace: dict[str, int] = {}
        # per-query cost attribution (selftrace root spans): per-tenant
        # totals of device ms, staged bytes, compiles, verified rows
        self.query_cost = Counter(
            "tempo_query_cost_total",
            help="per-tenant query cost totals by resource (device_ms, "
                 "staged_bytes, bytes_scanned, compiles, rows_verified)")
        self._query_costs: dict[str, dict[str, float]] = {}
        # per-query-class outcomes (ok / error / shed) recorded by the
        # frontend at every query exit: the availability SLI the SLO
        # engine (util/slo) evaluates. Sheds are a separate outcome --
        # a per-tenant QoS budget refusing work is the admission system
        # functioning, not the serving path failing, so the
        # availability objective excludes them.
        self.query_outcomes = Counter(
            "tempo_query_outcomes_total",
            help="frontend queries by op and outcome (ok/error/shed)")
        # resilience plane (PR 14): hedge outcomes (win = the hedge
        # twin finished first; lose = the original won after the twin
        # started; unneeded = the original won before the twin ran),
        # and per-query retry-budget consumption (retry = a shard
        # retry was granted; budget_exhausted = a retryable failure
        # was refused because the query's budget ran dry)
        self.hedge_total = Counter(
            "tempo_hedge_total",
            help="frontend hedged jobs by outcome (win/lose/unneeded)")
        self.retry_total = Counter(
            "tempo_retry_total",
            help="frontend shard retries by outcome "
                 "(retry/budget_exhausted)")
        self._hedges: dict[str, int] = {}
        self._retries: dict[str, int] = {}
        # tiered cache plane (PR 20): Tier A frontend result cache
        # (services/resultcache) and Tier B host-RAM compressed
        # column-chunk pool under the HBM staged cache (ops/chunkpool)
        self.result_cache_hits = Counter(
            "tempo_result_cache_hits_total",
            help="frontend result-cache hits served without touching "
                 "QoS budgets, the queue, or a device")
        self.result_cache_misses = Counter(
            "tempo_result_cache_misses_total",
            help="frontend result-cache misses (full execution)")
        self.result_cache_extensions = Counter(
            "tempo_result_cache_extensions_total",
            help="now-edge queries answered by extending a cached "
                 "immutable prefix with a tail-only execution")
        self.result_cache_invalidations = Counter(
            "tempo_result_cache_invalidations_total",
            help="result-cache entries invalidated by a blocklist or "
                 "live-head generation change")
        self.result_cache_bytes = Gauge(
            "tempo_result_cache_bytes",
            help="bytes held by the frontend result cache")
        self.chunk_cache_hits = Counter(
            "tempo_chunk_cache_hits_total",
            help="staged-column restages served from the host-RAM "
                 "compressed demote pool (no backend read)")
        self.chunk_cache_misses = Counter(
            "tempo_chunk_cache_misses_total",
            help="demote-pool probes that fell through to the backend")
        self.chunk_cache_demotions = Counter(
            "tempo_chunk_cache_demotions_total",
            help="staged-column entries demoted (recompressed) into the "
                 "host pool on HBM eviction instead of discarded")
        self.chunk_cache_evictions = Counter(
            "tempo_chunk_cache_evictions_total",
            help="demote-pool entries evicted by the host-RAM budget")
        self.chunk_cache_bytes = Gauge(
            "tempo_chunk_cache_bytes",
            help="compressed bytes held by the demote pool")
        # every instrument exported through /metrics -- ONE list shared
        # by metrics_lines() and help_entries() so an instrument can't
        # ship samples without its HELP (or vice versa)
        self._instruments = (
            self.compiles, self.cache_hits, self.device_time,
            self.transfer_bytes, self.staged_rows_real,
            self.staged_rows_padded, self.staged_cache_hits,
            self.staged_cache_misses, self.staged_cache_evictions,
            self.staged_cache_evicted_bytes, self.staged_column_hits,
            self.staged_column_misses, self.staged_bytes_reused,
            self.routing,
            self.batch_groups, self.batch_queries,
            self.batch_occupancy, self.batch_window_wait,
            self.batch_demux, self.mesh_batch_launches,
            self.mesh_batch_queries, self.mesh_batch_occupancy,
            self.compact_stage_time,
            self.compact_jobs, self.compact_input_bytes,
            self.compact_prefetch, self.compact_jobs_inflight,
            self.compact_bytes_inflight, self.compact_queue_depth,
            self.compact_passthrough_bytes, self.stream_stage_time,
            self.stream_units, self.stream_bytes_inflight,
            self.affinity_jobs, self.affinity_warm_steals, self.qos_shed,
            self.staged_placement,
            self.livestage_rows, self.livestage_delta_bytes,
            self.livestage_lag, self.ingest_stage_time,
            self.generator_stage_time, self.generator_freshness,
            self.generator_shed,
            self.selftrace_spans, self.query_cost,
            self.query_outcomes, self.hedge_total, self.retry_total,
            self.result_cache_hits, self.result_cache_misses,
            self.result_cache_extensions, self.result_cache_invalidations,
            self.result_cache_bytes, self.chunk_cache_hits,
            self.chunk_cache_misses, self.chunk_cache_demotions,
            self.chunk_cache_evictions, self.chunk_cache_bytes,
        )
        # full compile-key signatures, LRU-bounded (SEEN_SIGNATURES_MAX)
        self._seen: OrderedDict = OrderedDict()
        # (op, bucket-label) -> aggregate row for /status/kernels
        self._kernels: dict[tuple[str, str], dict] = {}
        self._routing: dict[tuple[str, str, str], int] = {}
        self._queries: deque = deque(maxlen=QUERY_LOG_SIZE)

    # ------------------------------------------------------------ config
    def sync_timing(self) -> bool:
        """Whether device timers block_until_ready (true device time) or
        record dispatch time only. Resolved once per process."""
        if self._sync is None:
            env = os.environ.get("TEMPO_KERNELTEL_SYNC", "")
            if env in ("0", "1"):
                self._sync = env == "1"
            else:
                from .linkcost import link_rtt_ms

                self._sync = link_rtt_ms() <= SYNC_RTT_MS
        return self._sync

    # ------------------------------------------------------------ stages
    def stage(self, name: str, **attrs) -> _Stage:
        """`with TEL.stage("<layer>:<stage>", **attrs):` -- see the
        module docstring. Names are a fixed vocabulary (they key a
        table); what varies (block id, bucket, bytes) is an attr."""
        return _Stage(self, name, attrs)

    def launch(self, op: str, key, bucket, cost=None, **attrs) -> _Launch:
        """`with TEL.launch(op, key, bucket, cost=...) as ln: out = fn(...)`
        -- record_launch, a `kernel:launch` stage (op, bucket, compile)
        around the dispatch, and the op's device-time window on exit.
        Dispatch-only programs call `ln.sync(out)` before leaving."""
        new = self.record_launch(op, key, bucket, cost)
        return _Launch(self, "kernel:launch",
                       dict(attrs, op=op, bucket=str(bucket), compile=new))

    def _add_stage(self, name: str, seconds: float,
                   cpu_seconds: float | None = None) -> None:
        with self._lock:
            row = self._stages.get(name)
            if row is None:
                # the stage families with a histogram of their own
                # (dashboards and alerts in ops/ read them by `stage`)
                layer, _, stage = name.partition(":")
                hist = {"ingest": self.ingest_stage_time,
                        "stream": self.stream_stage_time,
                        "generator": self.generator_stage_time}.get(layer)
                row = self._stages[name] = [0, 0.0, hist, f'stage="{stage}"', None]
            row[0] += 1
            row[1] += seconds
            if cpu_seconds is not None:
                row[4] = (row[4] or 0.0) + cpu_seconds
        if row[2] is not None:
            row[2].observe(seconds, row[3], exemplar=self._exemplar_tid())

    def mark_session(self) -> None:
        """A device-trace session starts: keep the table as it stands
        (`stages_at_session`), so that a reader can take what ran before
        the session apart from what ran beside its stop (seconds of CPU
        next to serving: PERF.md)."""
        self._stages_at_session = self.stage_stats()

    def stage_stats(self, layer: str | None = None) -> dict:
        """The cumulative stages table: {name: {count, seconds,
        cpu_seconds}}; `cpu_seconds` (the stage's thread on a CPU) only
        on rows that `with TEL.stage` bodies filled -- absent means not
        measured, never 0. With `layer`, that layer's rows keyed by the
        bare stage name (the `ingest.stages` / `generator.stages` /
        `stream.stage_seconds` shapes of /status/kernels)."""
        with self._lock:
            rows = {n: (r[0], r[1], r[4]) for n, r in self._stages.items()}
        if layer is not None:
            rows = {n.partition(":")[2]: r for n, r in rows.items()
                    if n.startswith(layer + ":")}
        out = {}
        for n, (c, s, cpu) in sorted(rows.items()):
            out[n] = {"count": c, "seconds": round(s, 6)}
            if cpu is not None:
                out[n]["cpu_seconds"] = round(cpu, 6)
        return out

    # ----------------------------------------------------------- kernels
    def record_launch(self, op: str, key, bucket, cost=None) -> bool:
        """Note one kernel launch. `key` is the full compile signature
        (everything that keys the jitted program: tree/cond structure +
        every padded axis bucket); `bucket` is the primary shape bucket
        used as the metric label. Returns True on a new compile.

        `cost`: zero-arg callable returning a costmodel.ProgramSpec --
        invoked only on a NEW compile, so the program's XLA cost
        analysis (and, for mesh programs, its collective comm bytes)
        is captured once in the costmodel's background worker. Every
        launch (new or cached) also ticks the costmodel's launch
        counter, which turns static per-program comm bytes into the
        tempo_mesh_comm_bytes_total series."""
        if _chaos.is_active():
            # chaos launch shim (ops/device.launch_tap): deliberately
            # OUTSIDE the swallow-everything block below -- an injected
            # compile failure / device OOM must reach the caller like a
            # real one would
            from ..ops.device import launch_tap

            launch_tap(op)
        blab = str(bucket)
        try:
            with self._lock:
                new = key not in self._seen
                if new:
                    self._seen[key] = True
                    while len(self._seen) > SEEN_SIGNATURES_MAX:
                        self._seen.popitem(last=False)
                else:
                    self._seen.move_to_end(key)
                k = self._kernels.get((op, blab))
                if k is None:
                    k = self._kernels[(op, blab)] = {
                        "compiles": 0, "cache_hits": 0, "calls": 0,
                        "device_seconds": 0.0, "last_compile_unix": 0.0,
                    }
                if new:
                    k["compiles"] += 1
                    k["last_compile_unix"] = time.time()
                else:
                    k["cache_hits"] += 1
            labels = f'op="{op}",bucket="{blab}"'
            (self.compiles if new else self.cache_hits).inc(labels=labels)
            self._tls.last = (op, blab, new)
            self.add_query_cost("compiles" if new else "cache_hits", 1)
            try:
                from .costmodel import COST

                COST.note_launch(op, blab)
                if new and cost is not None:
                    COST.enqueue(op, blab, cost())
            except Exception:
                pass  # cost capture must not flip the compile verdict
            if new:
                try:
                    # AOT warmup corpus: every first compile of an (op,
                    # bucket) pair is remembered in the CostLedger so a
                    # restarted process can pre-compile it (--warmup.shapes)
                    from .warmup import note_compile

                    note_compile(op, blab)
                except Exception:
                    pass
            return new
        except Exception:
            return False

    def last_launch(self) -> tuple[str, str, bool] | None:
        """(op, bucket, compiled) of this thread's most recent launch --
        lets the search layer stamp compile=true on the block's
        self-trace span without threading flags through every return."""
        return getattr(self._tls, "last", None)

    def observe_device(self, op: str, bucket, t0: float, out=None):
        """Close a device timing window opened at perf_counter() t0.
        With sync timing on and device outputs given, waits for them
        first so the window covers device execution, not just dispatch.
        Returns `out` for call-site chaining."""
        try:
            if out is not None and self.sync_timing():
                import jax

                jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            self.device_time.observe(dt, f'op="{op}"',
                                     exemplar=self._exemplar_tid())
            self.add_query_cost("device_ms", dt * 1e3)
            with self._lock:
                k = self._kernels.get((op, str(bucket)))
                if k is not None:
                    k["calls"] += 1
                    k["device_seconds"] += dt
        except Exception:
            pass
        return out

    def credit_device(self, op: str, bucket, seconds: float) -> None:
        """Credit a kernel-table row with one call and a share of a
        batch's timing window WITHOUT a histogram observation -- for
        call sites that launch several per-bucket programs under one
        measured window (the batched find loop)."""
        try:
            with self._lock:
                k = self._kernels.get((op, str(bucket)))
                if k is not None:
                    k["calls"] += 1
                    k["device_seconds"] += seconds
        except Exception:
            pass

    # ----------------------------------------------------------- staging
    def record_transfer(self, nbytes: int, rows_real: int, rows_padded: int) -> None:
        self.transfer_bytes.inc(nbytes)
        self.staged_rows_real.inc(rows_real)
        self.staged_rows_padded.inc(rows_padded)
        self.add_query_cost("staged_bytes", nbytes)

    # ----------------------------------------------------------- routing
    def record_routing(self, layer: str, engine: str, reason: str, n: int = 1) -> None:
        """One engine decision: which engine ran (or why the fast path
        fell back) and the reason the router chose it."""
        self.routing.inc(
            n, labels=f'layer="{layer}",engine="{engine}",reason="{reason}"')
        with self._lock:
            key = (layer, engine, reason)
            self._routing[key] = self._routing.get(key, 0) + n

    def routing_counts(self) -> dict[tuple[str, str, str], int]:
        with self._lock:
            return dict(self._routing)

    # ---------------------------------------------------------- batching
    def record_batch(self, name: str, occupancy: int, window_wait_s: float) -> None:
        """One fused batch group executed: its occupancy (queries per
        launch group) and the admission-window wait its leader paid."""
        try:
            labels = f'exec="{name}"'
            self.batch_groups.inc(labels=labels)
            self.batch_queries.inc(occupancy, labels=labels)
            self.batch_occupancy.observe(float(occupancy), labels)
            self.batch_window_wait.observe(float(window_wait_s), labels,
                                           exemplar=self._exemplar_tid())
            with self._lock:
                b = self._batches.setdefault(
                    name, {"groups": 0, "queries": 0, "max_occupancy": 0})
                b["groups"] += 1
                b["queries"] += int(occupancy)
                b["max_occupancy"] = max(b["max_occupancy"], int(occupancy))
        except Exception:
            pass

    def record_mesh_batch(self, occupancy: int) -> None:
        """One batched mesh launch executed: the whole window ran as a
        single Q-programs x sharded-rows program across every chip."""
        try:
            self.mesh_batch_launches.inc()
            self.mesh_batch_queries.inc(occupancy)
            self.mesh_batch_occupancy.observe(float(occupancy))
            with self._lock:
                mb = self._mesh_batches
                mb["launches"] += 1
                mb["queries"] += int(occupancy)
                mb["max_occupancy"] = max(mb["max_occupancy"], int(occupancy))
        except Exception:
            pass

    def mesh_batch_stats(self) -> dict:
        """Mesh-batch aggregates for /status/kernels and the bench row:
        occupancy = queries per mesh launch (1.0 = no amortization)."""
        with self._lock:
            mb = dict(self._mesh_batches)
        mb["occupancy"] = round(
            mb["queries"] / mb["launches"], 3) if mb["launches"] else 0.0
        return mb

    def record_demux(self, name: str, n: int = 1) -> None:
        try:
            self.batch_demux.inc(n, labels=f'exec="{name}"')
        except Exception:
            pass

    def batch_stats(self) -> dict:
        """Per-executor batching aggregates for /status/kernels.
        coalesce_ratio = queries per fused group (1.0 = no coalescing)."""
        with self._lock:
            out = {}
            for name, b in self._batches.items():
                out[name] = dict(b)
                out[name]["coalesce_ratio"] = round(
                    b["queries"] / b["groups"], 3) if b["groups"] else 0.0
            return out

    # --------------------------------------------------------- compaction
    def record_compact_stage(self, stage: str, seconds: float) -> None:
        """One pipeline stage (fetch/merge/assemble/write) finished for
        one job: observe its wall time."""
        try:
            self.compact_stage_time.observe(float(seconds), f'stage="{stage}"')
            with self._lock:
                ss = self._compaction["stage_seconds"]
                ss[stage] = ss.get(stage, 0.0) + float(seconds)
        except Exception:
            pass

    def record_compact_job(self, input_bytes: int, ok: bool = True) -> None:
        try:
            self.compact_jobs.inc(
                labels=f'outcome="{"ok" if ok else "error"}"')
            with self._lock:
                if ok:
                    self._compaction["jobs"] += 1
                    self._compaction["input_bytes"] += int(input_bytes)
                else:
                    self._compaction["errors"] += 1
            if ok:
                self.compact_input_bytes.inc(int(input_bytes))
        except Exception:
            pass

    def record_compact_prefetch(self, kind: str, n: int = 1) -> None:
        """Prefetch outcome: hit (worker found its inputs preloaded),
        miss (worker fetched them itself), waste (a prefetch attempt
        failed mid-IO and its work was thrown away -- the worker
        refetched from scratch)."""
        try:
            self.compact_prefetch.inc(n, labels=f'kind="{kind}"')
            with self._lock:
                p = self._compaction["prefetch"]
                p[kind] = p.get(kind, 0) + n
        except Exception:
            pass

    def compact_inflight(self, jobs: int, est_bytes: int, queued: int) -> None:
        """Point-in-time pipeline occupancy from the admission gate."""
        try:
            self.compact_jobs_inflight.set(jobs)
            self.compact_bytes_inflight.set(est_bytes)
            self.compact_queue_depth.set(queued)
            with self._lock:
                if jobs > self._compaction["max_jobs_inflight"]:
                    self._compaction["max_jobs_inflight"] = jobs
                if jobs > self._compaction["run_max_jobs_inflight"]:
                    self._compaction["run_max_jobs_inflight"] = jobs
        except Exception:
            pass

    def begin_compact_run(self) -> None:
        """Open one pipeline run: resets the run-scoped occupancy peak
        (the lifetime max stays monotonic)."""
        try:
            with self._lock:
                self._compaction["run_max_jobs_inflight"] = 0
        except Exception:
            pass

    def record_compact_run(self, wall_seconds: float) -> None:
        """Close one pipeline run (a whole admitted job set)."""
        try:
            with self._lock:
                self._compaction["runs"] += 1
                self._compaction["wall_seconds"] += float(wall_seconds)
        except Exception:
            pass

    def compaction_stats(self) -> dict:
        """Pipeline aggregates for /status/kernels and the bench rows.
        overlap_ratio = total stage seconds / run wall seconds: 1.0 means
        strictly sequential execution, >1 means stages (or jobs) actually
        overlapped in time."""
        with self._lock:
            c = {k: v for k, v in self._compaction.items()
                 if k not in ("stage_seconds", "prefetch")}
            c["stage_seconds"] = {
                k: round(v, 6)
                for k, v in self._compaction["stage_seconds"].items()}
            c["prefetch"] = dict(self._compaction["prefetch"])
        wall = c["wall_seconds"]
        stage_total = sum(c["stage_seconds"].values())
        c["overlap_ratio"] = round(stage_total / wall, 3) if wall > 0 else 0.0
        c["wall_seconds"] = round(wall, 6)
        c["jobs_inflight"] = int(self.compact_jobs_inflight.get())
        c["bytes_inflight"] = int(self.compact_bytes_inflight.get())
        c["queue_depth"] = int(self.compact_queue_depth.get())
        return c

    # ------------------------------------------------- cold-read streaming
    def record_stream_unit(self, outcome: str = "ok") -> None:
        """One pipeline unit reached a terminal state (ok / error /
        cancelled)."""
        try:
            self.stream_units.inc(labels=f'outcome="{outcome}"')
            with self._lock:
                if outcome == "ok":
                    self._stream["units"] += 1
                elif outcome == "cancelled":
                    self._stream["cancelled"] += 1
                else:
                    self._stream["errors"] += 1
        except Exception:
            pass

    def stream_inflight(self, est_bytes: int) -> None:
        try:
            self.stream_bytes_inflight.set(est_bytes)
        except Exception:
            pass

    def record_stream_run(self, wall_seconds: float) -> None:
        """Close one pipeline run (one streamed iterator drained)."""
        try:
            with self._lock:
                self._stream["runs"] += 1
                self._stream["wall_seconds"] += float(wall_seconds)
        except Exception:
            pass

    def stream_stats(self) -> dict:
        """Stream-pipeline aggregates for /status/kernels and the cold
        bench rows. overlap_ratio = total stage seconds / run wall
        seconds: <=1.0 means effectively sequential, >1 means stages of
        different units genuinely overlapped in time."""
        with self._lock:
            c = dict(self._stream)
        c["stage_seconds"] = {k: v["seconds"]
                              for k, v in self.stage_stats("stream").items()}
        wall = c["wall_seconds"]
        stage_total = sum(c["stage_seconds"].values())
        c["overlap_ratio"] = round(stage_total / wall, 3) if wall > 0 else 0.0
        c["wall_seconds"] = round(wall, 6)
        c["bytes_inflight"] = int(self.stream_bytes_inflight.get())
        return c

    # ------------------------------------------------- affinity scheduling
    def record_affinity(self, outcome: str, n: int = 1,
                        warm: bool = False) -> None:
        """One frontend dequeue under affinity routing: the job went to
        its owner ("own"), to another cache domain ("steal"), or carried
        no block affinity at all ("unowned"). `warm`: a steal taken
        BEFORE the steal timeout, by a domain that had reported the
        job's block among the blocks it holds staged columns for; the
        steals without it waited the timeout out."""
        try:
            self.affinity_jobs.inc(n, labels=f'outcome="{outcome}"')
            if warm:
                self.affinity_warm_steals.inc(n)
            with self._lock:
                self._affinity[outcome] = self._affinity.get(outcome, 0) + n
                if warm:
                    self._affinity_warm_steals += n
        except Exception:
            pass

    def record_shed(self, tenant: str, budget: str) -> None:
        """One query refused with 429 by a per-tenant QoS budget
        ("concurrency" or "bytes")."""
        try:
            tenant = tenant[:128]  # header-sourced: bound label size
            with self._lock:
                key = (tenant if (tenant in self._qos_sheds
                                  or len(self._qos_sheds) < QOS_SHED_TENANTS_MAX)
                       else "_overflow")
                t = self._qos_sheds.setdefault(key, {})
                t[budget] = t.get(budget, 0) + 1
            self.qos_shed.inc(
                labels=f'tenant="{_esc_label(key)}",budget="{budget}"')
        except Exception:
            pass

    def set_affinity_placement(self, placement: str):
        """Park the current job's dequeue placement for this execution
        context; returns a token for reset_affinity_placement."""
        return _affinity_placement.set(placement or "")

    def reset_affinity_placement(self, token) -> None:
        try:
            _affinity_placement.reset(token)
        except Exception:
            pass

    def record_staged_columns(self, hits: int, misses: int,
                              bytes_reused: int) -> None:
        """One staged-cache lookup by column: how many it found
        resident, how many it has to stage, and the bytes it reuses."""
        self.staged_column_hits.inc(hits)
        self.staged_column_misses.inc(misses)
        self.staged_bytes_reused.inc(bytes_reused)

    def record_staged_lookup(self, hit: bool) -> None:
        """One staged-cache probe, attributed to the ambient dequeue
        placement -- the owner-vs-stolen hit-rate split that says
        whether affinity routing is actually landing jobs on warm
        caches."""
        try:
            p = _affinity_placement.get() or "none"
            self.staged_placement.inc(
                labels=f'placement="{p}",result="{"hit" if hit else "miss"}"')
            with self._lock:
                row = self._staged_by_placement.setdefault(p, [0, 0])
                row[0 if hit else 1] += 1
        except Exception:
            pass

    # ------------------------------------------------------- job dispatch
    def record_stage(self, name: str, seconds: float) -> None:
        """One sample of a stage measured by its caller (an interval
        that no `with` body spans, e.g. enqueue -> handed to a worker):
        the `stages` table only; the caller adds the span."""
        try:
            self._add_stage(name, max(0.0, seconds))
        except Exception:
            pass

    def record_dispatch(self, worker: str, busy_s: float) -> None:
        """One job completed by `worker` ("local" = this process's own
        threads, else a remote querier's id), busy from the hand-off to
        its result."""
        with self._lock:
            self._dispatch_jobs["local" if worker == "local" else "remote"] += 1
            row = self._dispatch_workers.setdefault(worker, [0, 0.0])
            row[0] += 1
            row[1] += max(0.0, busy_s)

    def record_range(self, blocks: int, job_blocks: list[int]) -> None:
        """One search or metrics request planned over the blocklist:
        the blocks its range covers and the blocks of each job built for
        it (block-batch, row-group-shard and time-shard jobs; not the
        ingester leg)."""
        with self._lock:
            self._range_searches[blocks] = self._range_searches.get(blocks, 0) + 1
            self._range_jobs += len(job_blocks)
            self._range_job_blocks += sum(job_blocks)

    def range_stats(self) -> dict:
        with self._lock:
            return {"searches": sum(self._range_searches.values()),
                    "by_blocks": {str(b): n for b, n
                                  in sorted(self._range_searches.items())},
                    "jobs": self._range_jobs,
                    "job_blocks": self._range_job_blocks}

    def add_wire_bytes(self, n: int) -> None:
        with self._lock:
            self._dispatch_wire_bytes += int(n)

    def dispatch_stats(self) -> dict:
        with self._lock:
            return {"jobs": dict(self._dispatch_jobs),
                    "by_worker": {w: {"jobs": r[0],
                                      "busy_seconds": round(r[1], 6)}
                                  for w, r in sorted(self._dispatch_workers.items())},
                    "wire_bytes": self._dispatch_wire_bytes}

    def affinity_stats(self) -> dict:
        """Affinity + QoS aggregates for /status/kernels and the bench
        differential row."""
        with self._lock:
            staged = {
                p: {"hits": h, "misses": m,
                    "hit_rate": round(h / (h + m), 4) if h + m else 0.0}
                for p, (h, m) in sorted(self._staged_by_placement.items())
            }
            return {"jobs": dict(self._affinity),
                    "warm_steals": self._affinity_warm_steals,
                    "staged_by_placement": staged,
                    "qos_sheds": {t: dict(v)
                                  for t, v in sorted(self._qos_sheds.items())}}

    # ------------------------------------------------- live-head staging
    def set_livestage_rows(self, states: dict[str, int], rows: int,
                           generation: int) -> None:
        """Point-in-time occupancy after one staging refresh: slots by
        lifecycle state plus total membership rows."""
        try:
            with self._lock:
                gone = set(self._livestage["slots"]) - set(states)
                self._livestage["slots"] = dict(states)
                self._livestage["rows"] = int(rows)
                self._livestage["generation"] = int(generation)
            for state, n in states.items():
                self.livestage_rows.set(n, labels=f'state="{state}"')
            for state in gone:  # a drained state must read 0, not stale
                self.livestage_rows.set(0, labels=f'state="{state}"')
            self.livestage_rows.set(rows, labels='state="rows"')
        except Exception:
            pass

    def record_livestage_upload(self, nbytes: int, rows: int,
                                full: bool) -> None:
        """One refresh moved bytes over the host->device link (a delta
        append, or a full re-upload on bucket growth/compaction)."""
        try:
            self.livestage_delta_bytes.inc(nbytes)
            with self._lock:
                self._livestage["uploads"] += 1
                if full:
                    self._livestage["full_uploads"] += 1
                self._livestage["delta_bytes"] += int(nbytes)
                self._livestage["delta_rows"] += int(rows)
        except Exception:
            pass

    def record_staging_lag(self, seconds: float) -> None:
        """Push acknowledged -> segment staged (device-visible)."""
        try:
            self.livestage_lag.observe(float(seconds))
            with self._lock:
                ls = self._livestage
                ls["lag_count"] += 1
                ls["lag_sum"] += float(seconds)
                ls["lag_max"] = max(ls["lag_max"], float(seconds))
        except Exception:
            pass

    def livestage_stats(self) -> dict:
        """Live-head staging aggregates for /status/kernels, including
        the live-vs-host engine routing split."""
        with self._lock:
            out = dict(self._livestage)
            out["slots"] = dict(self._livestage["slots"])
            routing = {
                f"{layer}:{engine}:{reason}": n
                for (layer, engine, reason), n in sorted(self._routing.items())
                if layer in ("search_live", "find_live")
            }
        out["lag_avg_s"] = round(
            out["lag_sum"] / out["lag_count"], 6) if out["lag_count"] else 0.0
        out["lag_max_s"] = round(out.pop("lag_max"), 6)
        out.pop("lag_sum", None)
        out["routing"] = routing
        return out

    # ----------------------------------------------------------- ingest
    def record_ingest_window(self, traces: int, nbytes: int) -> None:
        """One push window appended to the columnar WAL."""
        try:
            with self._lock:
                self._ingest["windows"] += 1
                self._ingest["window_traces"] += int(traces)
                self._ingest["window_bytes"] += int(nbytes)
        except Exception:
            pass

    def record_ingest_features(self, entries: int) -> None:
        """Segment features checkpointed into the WAL."""
        try:
            with self._lock:
                self._ingest["feature_entries"] += int(entries)
        except Exception:
            pass

    def record_ingest_replay(self, records: int, features: int,
                             torn: bool = False) -> None:
        """One WAL file replayed at startup."""
        try:
            with self._lock:
                rp = self._ingest["replays"]
                rp["records"] += int(records)
                rp["features"] += int(features)
                if torn:
                    rp["torn"] += 1
        except Exception:
            pass

    def ingest_stats(self) -> dict:
        """Write-path aggregates for /status/kernels."""
        with self._lock:
            out = dict(self._ingest)
            out["replays"] = dict(self._ingest["replays"])
        out["stages"] = self.stage_stats("ingest")
        return out

    # -------------------------------------------------------- generator
    def record_generator_window(self, spans: int, edges: int,
                                unpaired: int = 0, expired: int = 0) -> None:
        """One push window folded: spans aggregated, service-graph
        edges completed, plus the edge store's current unpaired depth
        and cumulative expiries."""
        try:
            with self._lock:
                g = self._generator
                g["windows"] += 1
                g["window_spans"] += int(spans)
                g["edges_completed"] += int(edges)
                g["unpaired"] = int(unpaired)
                g["expired"] = int(expired)
        except Exception:
            pass

    def record_generator_shed(self, tenant: str, n: int) -> None:
        """Spans refused a new series by max-active-series."""
        try:
            self.generator_shed.inc(
                int(n), labels=f'tenant="{_esc_label(tenant)}"')
            with self._lock:
                sh = self._generator["shed"]
                sh[tenant] = sh.get(tenant, 0) + int(n)
        except Exception:
            pass

    def record_generator_freshness(self, seconds: float) -> None:
        """Push receive -> series visible, one window."""
        try:
            self.generator_freshness.observe(float(seconds))
            with self._lock:
                g = self._generator
                g["freshness_count"] += 1
                g["freshness_sum"] += float(seconds)
                g["freshness_max"] = max(g["freshness_max"], float(seconds))
        except Exception:
            pass

    def generator_stats(self) -> dict:
        """Streaming-generator aggregates for /status/kernels."""
        with self._lock:
            out = dict(self._generator)
            out["shed"] = dict(self._generator["shed"])
        out["stages"] = self.stage_stats("generator")
        out["freshness_avg_s"] = round(
            out["freshness_sum"] / out["freshness_count"],
            6) if out["freshness_count"] else 0.0
        out["freshness_max_s"] = round(out.pop("freshness_max"), 6)
        out.pop("freshness_sum", None)
        return out

    def record_passthrough(self, nbytes: int) -> None:
        """Compressed bytes a compaction output inherited verbatim."""
        try:
            self.compact_passthrough_bytes.inc(int(nbytes))
        except Exception:
            pass

    # --------------------------------------------------------- hedging
    def record_hedge(self, outcome: str) -> None:
        """One hedged job resolved: win / lose / unneeded."""
        try:
            self.hedge_total.inc(labels=f'outcome="{outcome}"')
            with self._lock:
                self._hedges[outcome] = self._hedges.get(outcome, 0) + 1
        except Exception:
            pass

    def record_retry(self, outcome: str) -> None:
        """One retry decision: retry (granted) / budget_exhausted."""
        try:
            self.retry_total.inc(labels=f'outcome="{outcome}"')
            with self._lock:
                self._retries[outcome] = self._retries.get(outcome, 0) + 1
        except Exception:
            pass

    def hedge_stats(self) -> dict:
        with self._lock:
            return dict(self._hedges)

    def retry_stats(self) -> dict:
        with self._lock:
            return dict(self._retries)

    # --------------------------------------------------------- query log
    def record_query(self, op: str, seconds: float, trace_id: str = "",
                     detail: str = "", outcome: str = "ok") -> None:
        try:
            self.query_outcomes.inc(
                labels=f'op="{op}",outcome="{outcome}"')
        except Exception:
            pass
        artifact = ""
        try:
            # slow-query auto-capture (util/profiler): latency past the
            # query class's SLO p99 threshold snapshots the sampler
            # ring into a folded artifact whose id rides the log entry
            # beside the self-trace id -- page -> /status/slo ->
            # slow-query log -> timeline + profile
            from .profiler import PROF

            if PROF.sampling:
                artifact = PROF.capture_slow_query(op, float(seconds),
                                                   trace_id)
        except Exception:
            artifact = ""
        with self._lock:
            self._queries.append({
                "op": op,
                "seconds": round(float(seconds), 6),
                "self_trace_id": trace_id,
                "profile_artifact_id": artifact,
                "detail": detail[:200],
                "outcome": outcome,
                "at_unix": round(time.time(), 3),
            })

    def slow_queries(self, k: int = 10) -> list[dict]:
        with self._lock:
            recent = list(self._queries)
        return sorted(recent, key=lambda q: -q["seconds"])[:k]

    # --------------------------------------------------- query cost record
    def add_query_cost(self, key: str, value: float) -> None:
        """Accumulate one cost dimension onto the ACTIVE self-trace (a
        no-op when no trace is parked): device ms, staged bytes,
        compiles, verified rows. Totals become `cost.*` root attrs at
        trace finish and fold into per-tenant counters here."""
        try:
            t = _active_trace.get()
            if t is not None:
                t.add_cost(key, value)
        except Exception:
            pass

    def record_query_cost(self, tenant: str, cost: dict) -> None:
        """Fold one finished query's cost record into the per-tenant
        aggregates (bounded tenant cardinality, like QoS sheds)."""
        try:
            tenant = (tenant or "_unknown")[:128]
            with self._lock:
                key = (tenant if (tenant in self._query_costs
                                  or len(self._query_costs) < QOS_SHED_TENANTS_MAX)
                       else "_overflow")
                t = self._query_costs.setdefault(key, {"queries": 0})
                t["queries"] += 1
                for k, v in cost.items():
                    t[k] = round(t.get(k, 0) + float(v), 3)
            esc = _esc_label(key)
            self.query_cost.inc(1, labels=f'tenant="{esc}",resource="queries"')
            for k, v in cost.items():
                self.query_cost.inc(
                    float(v), labels=f'tenant="{esc}",resource="{k}"')
        except Exception:
            pass

    def query_cost_stats(self) -> dict:
        with self._lock:
            return {t: dict(v) for t, v in sorted(self._query_costs.items())}

    # --------------------------------------------------------- self-trace
    def record_selftrace(self, outcome: str, n_spans: int) -> None:
        """Self-trace shipping outcome: `shipped` spans reached the
        distributor, `dropped` spans died with their trace at the
        bounded in-flight queue (TempoSelfTraceDropped alert feed)."""
        try:
            self.selftrace_spans.inc(n_spans, labels=f'outcome="{outcome}"')
            with self._lock:
                self._selftrace[outcome] = (
                    self._selftrace.get(outcome, 0) + n_spans)
        except Exception:
            pass

    def selftrace_stats(self) -> dict:
        with self._lock:
            return dict(self._selftrace)

    def _exemplar_tid(self) -> str | None:
        """The active self-trace's id for OpenMetrics exemplars (None
        when no trace is parked -- the histogram keeps its last one)."""
        try:
            t = _active_trace.get()
            tid = getattr(t, "trace_id", None)
            return tid.hex() if tid is not None else None
        except Exception:
            return None

    @staticmethod
    def _note_profiler_thread(trace) -> None:
        """Mirror the active trace into the profiler's thread registry
        (set/reset run ON the executing thread) so background samples
        attribute to the query. One attribute check when sampling is
        off -- the profiling-off path stays effectively free."""
        try:
            from .profiler import PROF

            if PROF.sampling:
                PROF.note_thread_trace(threading.get_ident(),
                                       getattr(trace, "trace_id", None))
        except Exception:
            pass

    def set_active_trace(self, trace):
        """Park the active SelfTracer trace for this execution context;
        returns a token for reset_active_trace."""
        token = _active_trace.set(trace)
        self._note_profiler_thread(trace)
        return token

    def reset_active_trace(self, token) -> None:
        try:
            _active_trace.reset(token)
        except Exception:
            pass
        # restore the registry to whatever the context now holds
        # (nested set/reset pairs land back on the outer trace)
        self._note_profiler_thread(_active_trace.get())

    def active_trace(self):
        return _active_trace.get()

    def child_span(self, name: str, t0: float, t1: float,
                   attrs: dict | None = None) -> None:
        """Attach one child span (wall-clock seconds) to the active
        self-trace, if any. Engine code calls this per block."""
        t = _active_trace.get()
        if t is not None:
            try:
                t.child(name, t0, t1, attrs or {})
            except Exception:
                pass  # observability must never fail a query

    # ----------------------------------------------------------- readout
    def jit_cache_size(self) -> int:
        with self._lock:
            return len(self._seen)

    def totals(self) -> tuple[int, float]:
        """(total compiles, total device seconds) -- bench deltas."""
        with self._lock:
            return (sum(k["compiles"] for k in self._kernels.values()),
                    sum(k["device_seconds"] for k in self._kernels.values()))

    def launch_count(self) -> int:
        """Total device-kernel launches recorded (compiles + jit-cache
        hits across every op) -- the batching tests and the concurrent
        bench measure launches-per-query as deltas of this."""
        with self._lock:
            return sum(k["compiles"] + k["cache_hits"]
                       for k in self._kernels.values())

    def snapshot(self, slow_k: int = 10) -> dict:
        """The /status/kernels payload."""
        with self._lock:
            kernels = [
                {"op": op, "bucket": b, **dict(stats)}
                for (op, b), stats in sorted(self._kernels.items())
            ]
            rows_real = self.staged_rows_real.get()
            rows_padded = self.staged_rows_padded.get()
            routing = [
                {"layer": l, "engine": e, "reason": r, "count": n}
                for (l, e, r), n in sorted(self._routing.items())
            ]
        return {
            "jit_cache": {
                "entries": self.jit_cache_size(),
                "compiles_total": sum(k["compiles"] for k in kernels),
                "cache_hits_total": sum(k["cache_hits"] for k in kernels),
            },
            "kernels": kernels,
            "staging": {
                "transfer_bytes_total": int(self.transfer_bytes.get()),
                "rows_real_total": int(rows_real),
                "rows_padded_total": int(rows_padded),
                "padding_waste_ratio": round(
                    rows_padded / rows_real, 4) if rows_real else 0.0,
                "cache_hits": int(self.staged_cache_hits.get()),
                "cache_misses": int(self.staged_cache_misses.get()),
                "column_hits": int(self.staged_column_hits.get()),
                "column_misses": int(self.staged_column_misses.get()),
                "bytes_reused": int(self.staged_bytes_reused.get()),
            },
            "routing": routing,
            "hedging": self.hedge_stats(),
            "retries": self.retry_stats(),
            "affinity": self.affinity_stats(),
            "dispatch": self.dispatch_stats(),
            "range": self.range_stats(),
            "query_costs": self.query_cost_stats(),
            "selftrace": self.selftrace_stats(),
            "batching": self.batch_stats(),
            "mesh_batch": self.mesh_batch_stats(),
            "compaction": self.compaction_stats(),
            "stream": self.stream_stats(),
            "livestage": self.livestage_stats(),
            "ingest": self.ingest_stats(),
            "generator": self.generator_stats(),
            "stages": self.stage_stats(),
            "stages_at_session": self._stages_at_session,
            "interp": interp_stats(),
            "slow_queries": self.slow_queries(slow_k),
        }

    def metrics_lines(self) -> list[str]:
        """Exposition sample lines for /metrics (kerneltel instruments
        plus the costmodel's program/comm/HBM families -- one
        chokepoint so /metrics can't ship one plane without the
        other)."""
        out: list[str] = []
        for inst in self._instruments:
            out += inst.text()
        try:
            from .costmodel import COST

            out += COST.metrics_lines()
        except Exception:
            pass
        # chaos + circuit-breaker planes ride the same exposition
        # chokepoint so /metrics can't ship one plane without the other
        try:
            out += _chaos.metrics_lines()
        except Exception:
            pass
        try:
            from . import breaker as _breaker

            out += _breaker.metrics_lines()
        except Exception:
            pass
        # continuous-profiling plane: sampler/lock-wait/log/runtime
        # families ride the same chokepoint, so every /metrics surface
        # (app, vulture sidecars) ships them with the rest
        try:
            from . import profiler as _profiler

            out += _profiler.metrics_lines()
        except Exception:
            pass
        try:
            from . import log as _log

            out += _log.metrics_lines()
        except Exception:
            pass
        try:
            from . import runtimestats as _rt

            out += _rt.metrics_lines()
        except Exception:
            pass
        return out

    def help_entries(self) -> dict[str, str]:
        """family -> help for the exposition renderer."""
        out = {}
        for inst in self._instruments:
            fam = inst.name[:-6] if inst.name.endswith("_total") else inst.name
            out[fam] = inst.help
        try:
            from .costmodel import COST

            out.update(COST.help_entries())
        except Exception:
            pass
        try:
            out.update(_chaos.help_entries())
        except Exception:
            pass
        try:
            from . import breaker as _breaker

            out.update(_breaker.help_entries())
        except Exception:
            pass
        try:
            from . import profiler as _profiler

            out.update(_profiler.help_entries())
        except Exception:
            pass
        try:
            from . import log as _log

            out.update(_log.help_entries())
        except Exception:
            pass
        try:
            from . import runtimestats as _rt

            out.update(_rt.help_entries())
        except Exception:
            pass
        return out

    def reset(self) -> None:
        """Fresh state (tests). Callers must reference instruments via
        TEL attributes, never cache them across a reset. The costmodel's
        launch/program tables reset with the kernel table they mirror."""
        self.__init__()
        try:
            from .costmodel import COST

            COST.reset()
        except Exception:
            pass


TEL = KernelTelemetry()
