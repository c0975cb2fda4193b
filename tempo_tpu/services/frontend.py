"""Query frontend: per-tenant fair queue, job sharding, retry, combine.

Reference: modules/frontend -- trace-by-ID pipeline (deduper->sharder->
retry, frontend.go:96-183), search sharder (searchsharding.go:69-247),
trace-ID-space sharding (tracebyidsharding.go:30-48), and the per-tenant
queue queriers pull from (v1/frontend.go:50-90, pkg/scheduler/queue).

Jobs carry BOTH a local closure (in-process worker threads, the
single-binary fast path) and a wire form (kind + payload): standalone
querier processes attach over HTTP long-poll (/internal/jobs/poll) and
pull the same queue the local workers drain -- the reference's
querier-worker frontend_processor loop (frontend_processor.go:57-80),
dispatcher and execution fully decoupled.

Search jobs are block BATCHES, not 10-MiB page shards: the device
engine answers a whole batch of blocks in one fused program + one
device sync (db/search.search_blocks_fused), so the unit of dispatch
is sized to amortize the sync, not to bound a Go worker's scan time.
Oversized single blocks still shard by row-group range.

Cache-affinity scheduling: block-carrying jobs hash their lead block ID
onto a consistent-hash ring over the live cache domains (this process's
local worker pool + every attached remote querier), and the dequeue
prefers handing a job to its affinity owner so a block staged in one
querier's HBM (ops/stage staged cache) stays staged there instead of
being re-fetched, re-padded and re-uploaded by whichever worker happens
to poll first. Locality is satisfied by any replica: a domain that
reports the job's block among the blocks it holds staged columns for
(a querier says so with every poll) may take the job at once. For the
rest a bounded anti-starvation steal timeout
(TEMPO_AFFINITY_STEAL_MS) lets any worker take a job its owner hasn't
claimed in time, so a slow or dead owner never strands work; with
affinity off (TEMPO_AFFINITY=0) or a single cache domain the dequeue
path is exactly the legacy head-of-queue behavior.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field

from ..db.search import (
    SearchRequest,
    SearchResponse,
    request_to_dict,
    response_from_dict,
)
from .. import config_registry as _cfg
from ..ring.ring import InMemoryKV, InstanceDesc, InstanceState, Ring, deterministic_tokens
from ..util.breaker import CircuitOpen, RetryBudget, get_breaker
from ..util.profiler import timed_lock
from ..wire.combine import combine_traces, sort_trace
from .overrides import QueryAdmission
from .querier import Querier

TARGET_BATCH_BYTES = 256 << 20  # block-batch job size (device engine unit)
DEFAULT_CONCURRENT_JOBS = 50
MAX_RETRIES = 3
MAX_BLOCKS_PER_BATCH = 64
FIND_SHARD_BLOCKS = 16  # candidate blocks per ID-shard find job

# job kinds that scan backend blocks: the legs the backend circuit
# breaker guards (a shed search shard degrades coverage via the
# existing failed-shard tolerance; find/metrics fail fast -- their
# shard-loss rules forbid silent partials -- instead of hammering a
# dying backend)
BACKEND_KINDS = frozenset(
    {"search_blocks", "search_block_shard", "find_blocks",
     "metrics_query_range"})

# dequeue placements as the dispatch spans spell them
_PLACEMENT_NAMES = {"own": "owner", "steal": "stolen", "unowned": "unowned"}

AFFINITY_RING_KEY = "querier-affinity"
AFFINITY_STEAL_MS = 75.0  # default anti-starvation steal timeout
AFFINITY_SCAN_WINDOW = 64  # queued jobs per tenant an affinity scan inspects


class TooManyRequests(Exception):
    pass


def _retryable(e: Exception) -> bool:
    """Transient (IO / backend / timeout) errors retry; deterministic
    failures (parse errors, bad values) fail fast."""
    from ..backend.base import BackendError, DoesNotExist

    if isinstance(e, DoesNotExist):
        return False  # deterministic: the object is gone
    return isinstance(e, (OSError, TimeoutError, ConnectionError, BackendError))


class RequestQueue:
    """Per-tenant fair FIFO: tenants round-robin, jobs FIFO within a
    tenant (pkg/scheduler/queue/queue.go). Drained tenants are pruned
    from the rotation (a churning tenant population used to grow
    self.order without bound, and every dequeue scanned the corpses)."""

    CLAIM_RECHECK_S = 0.02  # re-scan cadence while steal clocks run

    def __init__(self, max_per_tenant: int = 2000):
        # cataloged hot lock: every enqueue/dequeue (and the affinity
        # claim scan) serializes here; TEMPO_LOCK_PROFILE arms wait
        # timing. The Condition wraps the same lock either way.
        self.lock = timed_lock("frontend_queue")
        self.cv = threading.Condition(self.lock)
        self.queues: dict[str, deque] = {}
        self.order: deque[str] = deque()
        self.max_per_tenant = max_per_tenant
        self.closed = False

    def enqueue(self, tenant: str, job) -> None:
        with self.cv:
            q = self.queues.get(tenant)
            if q is None:
                q = self.queues[tenant] = deque()
                self.order.append(tenant)
            if len(q) >= self.max_per_tenant:
                raise TooManyRequests(f"tenant {tenant} queue full")  # 429
            q.append(job)
            try:
                # a re-dispatched job must not carry its previous
                # dequeue's placement: the next dequeue stamps its own
                # (or none, on the legacy path) -- stale "own" would
                # double-count affinity telemetry and misattribute
                # staged-cache lookups on the retry worker
                job.placement = ""
                job.warm = False
            except AttributeError:
                pass
            if getattr(job, "queued_at", None) == 0.0:
                # steal clock starts at FIRST enqueue only: a hedged
                # twin keeps its original stamp (long past the steal
                # window by hedge time) and a retried job is demoted to
                # placement-free by the retry paths -- re-dispatch
                # exists precisely to dodge the owner that failed it
                job.queued_at = time.monotonic()
            # notify_all, not notify: under affinity a single wakeup can
            # land on a non-owner that defers the job and goes back to
            # waiting -- the sleeping owner would never hear about its
            # own job and every dequeue would pay the steal timeout
            self.cv.notify_all()

    def depths(self) -> dict[str, int]:
        """Per-tenant queue depth snapshot -- the fleet's autoscaling
        SLI (tempo_query_queue_depth): sustained depth means the
        querier pool is under-provisioned for the offered load."""
        with self.cv:
            return {t: len(q) for t, q in self.queues.items() if q}

    def _prune_locked(self, tenant: str, q) -> None:
        """Drop a drained tenant from both maps (invariant: a tenant is
        in self.order iff it has a non-empty deque)."""
        if not q:
            self.queues.pop(tenant, None)
            try:
                self.order.remove(tenant)
            except ValueError:
                pass

    def dequeue(self, timeout: float = 0.5, allowed=None, claim=None):
        """Next (tenant, job), fair across tenants; allowed(tenant) False
        skips a tenant for THIS caller (per-tenant querier shuffle-shard,
        pkg/scheduler/queue/user_queues.go). claim(tenant, job, now) ->
        placement string | None gates WHICH job this caller may take (block->
        querier affinity): the first claimable job within
        AFFINITY_SCAN_WINDOW of each tenant's FIFO is taken and stamped
        with its placement; jobs deferred to their owner are re-checked
        every CLAIM_RECHECK_S so steal timeouts fire without a notify.
        claim=None (affinity off / single cache domain) is exactly the
        legacy head-of-queue path."""
        with self.cv:
            if claim is None:
                while True:
                    item = self._take_head_locked(allowed)
                    if item is not None:
                        return item
                    if self.closed:
                        return None
                    if not self.cv.wait(timeout):
                        return None
            deadline = time.monotonic() + timeout
            while True:
                item, deferred = self._take_claimed_locked(allowed, claim)
                if item is not None:
                    return item
                if self.closed:
                    return None
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self.cv.wait(min(remaining, self.CLAIM_RECHECK_S)
                             if deferred else remaining)

    def _take_head_locked(self, allowed):
        """One fair pass taking the head job of the first allowed
        tenant -- the pre-affinity dequeue, byte for byte."""
        n = len(self.order)
        scanned = 0
        while scanned < n:
            tenant = self.order[0]
            q = self.queues.get(tenant)
            if not q:
                # drained (or orphaned) rotation slot: prune it
                self.order.popleft()
                self.queues.pop(tenant, None)
                n -= 1
                continue
            self.order.rotate(-1)
            scanned += 1
            if allowed is None or allowed(tenant):
                job = q.popleft()
                self._prune_locked(tenant, q)
                return tenant, job
        return None

    def _take_claimed_locked(self, allowed, claim):
        """One fair pass under affinity: per tenant, take the first job
        (within the scan window) the claimer may have. claim(tenant,
        job, now) sees the tenant because ownership is resolved within
        the tenant's reachable worker subset (querier shuffle-shard).
        Returns ((tenant, job), False) or (None, deferred) where
        deferred means jobs exist that only their owner (or the steal
        clock) can release."""
        now = time.monotonic()
        deferred = False
        n = len(self.order)
        scanned = 0
        while scanned < n:
            tenant = self.order[0]
            q = self.queues.get(tenant)
            if not q:
                self.order.popleft()
                self.queues.pop(tenant, None)
                n -= 1
                continue
            self.order.rotate(-1)
            scanned += 1
            if allowed is not None and not allowed(tenant):
                continue
            for i, job in enumerate(q):
                if i >= AFFINITY_SCAN_WINDOW:
                    break
                placement = claim(tenant, job, now)
                if placement:
                    del q[i]
                    try:
                        job.placement = placement
                    except AttributeError:
                        pass
                    self._prune_locked(tenant, q)
                    return (tenant, job), False
            deferred = True
        return None, deferred

    def dequeue_batch(self, timeout: float = 0.5, allowed=None,
                      max_batch: int = 1, key_fn=None, claim=None):
        """Fair dequeue of one job plus up to max_batch-1 ALREADY-QUEUED
        jobs sharing its coalesce key (key_fn(job), None = unbatchable),
        collected in one pass over the tenant rotation -- fairness within
        the window means every tenant's matching head jobs join the same
        fused launch rather than queueing behind it. Never waits for
        more jobs, so a lone query is never delayed here (the admission
        window lives in db/batchexec). Under affinity (claim) the
        same-key extras ride the lead's claim wherever they sit in the
        scan window: same blocks means same owner, so a coalesced
        multi-query launch lands whole on the warm staged cache.
        Returns (tenant, job, extras) where extras is a list of
        (tenant, job)."""
        item = self.dequeue(timeout, allowed, claim=claim)
        if item is None:
            return None
        tenant, job = item
        extras: list = []
        key = key_fn(job) if key_fn is not None else None
        if key is not None and max_batch > 1:
            lead_placement = getattr(job, "placement", "")
            lead_warm = getattr(job, "warm", False)
            with self.cv:
                for _ in range(len(self.order)):
                    if len(extras) >= max_batch - 1 or not self.order:
                        break
                    t2 = self.order[0]
                    self.order.rotate(-1)
                    q = self.queues.get(t2)
                    if not q or (allowed is not None and not allowed(t2)):
                        continue
                    if claim is None:
                        while (q and len(extras) < max_batch - 1
                               and key_fn(q[0]) == key):
                            extras.append((t2, q.popleft()))
                    else:
                        i = 0
                        while (i < min(len(q), AFFINITY_SCAN_WINDOW)
                               and len(extras) < max_batch - 1):
                            if key_fn(q[i]) == key:
                                j2 = q[i]
                                del q[i]
                                try:
                                    j2.placement = lead_placement
                                    j2.warm = lead_warm
                                except AttributeError:
                                    pass
                                extras.append((t2, j2))
                            else:
                                i += 1
                    self._prune_locked(t2, q)
        return tenant, job, extras

    def close(self):
        with self.cv:
            self.closed = True
            self.cv.notify_all()


@dataclass
class _Job:
    kind: str  # wire kind: search_recent|search_blocks|search_block_shard|
    # find_recent|find_blocks
    payload: dict  # wire-shippable arguments (ids, not objects)
    fn: object  # local execution closure (in-process workers)
    args: tuple
    result: object = None
    error: Exception | None = None
    done: threading.Event = field(default_factory=threading.Event)
    tries: int = 0
    cancelled: bool = False
    hedged: bool = False
    started_wall: float = 0.0  # wall clock (self-trace spans)
    done_at: float = 0.0  # wall clock
    batch_cv: threading.Condition | None = None
    # active SelfTracer trace, parked in the kerneltel contextvar around
    # local execution so engine code can attach per-block kernel spans;
    # span_id is this job's PRE-ASSIGNED span in that trace (engine
    # spans nest under it; remote legs parent onto it over the wire),
    # dequeued_wall closes the queue-wait span
    trace: object = None
    span_id: bytes = b""
    dequeued_wall: float = 0.0
    # cross-query coalescing: jobs sharing a non-None batch_key target
    # the same data unit (block batch / shard / candidate partition) and
    # may execute together via batch_fn(group) -> list of results
    batch_key: tuple | None = None
    batch_fn: object = None
    # progressive delivery: a streaming collector's condition, notified
    # (in addition to batch_cv) whenever this job finishes so partial
    # results flush to the client as each shard completes
    stream_cv: threading.Condition | None = None
    # cache-affinity scheduling: the block ID this job's placement
    # hashes on (None = placement-free, claimable by anyone), the
    # monotonic stamp its steal clock runs from (set at first enqueue),
    # and the dequeue outcome ("own"/"steal"/"unowned") it executed under;
    # warm = a steal taken before the clock by a domain that holds the block
    affinity_key: str | None = None
    # what the job's `job:<kind>` self-trace span says of its size
    span_attrs: dict = field(default_factory=dict)
    queued_at: float = 0.0
    placement: str = ""
    warm: bool = False
    # resilience plane: the query-wide retry budget this job draws
    # from, the caller's wall-clock deadline (rides the wire job so
    # remote workers skip work nobody can use), and hedge attribution
    # (exec_seq counts dispatches; the leg that lands the result says
    # whether the hedge twin won, lost, or never even started)
    retry_budget: object = None
    deadline_unix: float = 0.0
    exec_seq: int = 0
    hedge_started: bool = False
    hedge_outcome: str = ""
    lease_redispatched: bool = False  # re-enqueued by lease expiry
    # job dispatch: who had the job in hand ("local" = this process's
    # threads, else the remote querier's id), when (wall clock; for a
    # remote leg the querier's own stamp of receiving it; the hedge clock
    # runs from here, so queue wait never hedges a job), and when its
    # result was posted and merged -- the `job:dispatch` / `job:result`
    # spans of the timeline (the last hand-off, for a hedged job)
    worker: str = ""
    handed_wall: float = 0.0
    posted_wall: float = 0.0
    merged_wall: float = 0.0

    def finish(self) -> None:
        if not self.done.is_set():  # a late hedge twin must not clobber
            self.done_at = time.time()  # the winner's end time
        self.done.set()
        cv = self.batch_cv
        if cv is not None:
            with cv:
                cv.notify_all()
        scv = self.stream_cv
        if scv is not None:
            with scv:
                scv.notify_all()


def attach_trace(jobs: list, trace) -> None:
    """Bind jobs to the active self-trace, pre-assigning each job's
    span id so the span EXISTS as an address before the job runs:
    nested engine spans and remote-leg spans parent onto it, and
    _emit_self_trace materializes it retroactively with the measured
    times."""
    if trace is None:
        return
    for j in jobs:
        j.trace = trace
        j.span_id = os.urandom(8)


def decode_job_result(kind: str, out: dict):
    """Wire result -> the object the local closure would have returned."""
    if kind == "metrics_query_range":
        from ..db.metrics_exec import response_from_dict as metrics_response_from_dict

        return metrics_response_from_dict(out)
    if kind.startswith("search"):
        return response_from_dict(out)
    tr = out.get("trace")
    if not tr:
        return None
    from ..wire import otlp_json

    return otlp_json.loads(tr)


class Frontend:
    """Owns the queue + sharding logic; local worker threads and remote
    querier processes both pull from the queue."""

    def __init__(self, querier: Querier, n_workers: int = 8,
                 concurrent_jobs: int = DEFAULT_CONCURRENT_JOBS,
                 batch_bytes: int = TARGET_BATCH_BYTES,
                 hedge_after_s: float = 2.0,
                 lease_s: float = 30.0,
                 overrides=None,
                 worker_expiry_s: float = 60.0,
                 affinity: bool | None = None,
                 affinity_steal_ms: float | None = None):
        self.querier = querier
        self.queue = RequestQueue()
        self.concurrent_jobs = concurrent_jobs
        self.batch_bytes = batch_bytes
        self.hedge_after_s = hedge_after_s
        self.lease_s = lease_s
        self.overrides = overrides
        self.worker_expiry_s = worker_expiry_s
        # block->querier affinity routing (None = TEMPO_AFFINITY env,
        # default on; it is a no-op until a second cache domain appears)
        if affinity is None:
            affinity = os.environ.get("TEMPO_AFFINITY", "") != "0"
        self.affinity_enabled = affinity
        if affinity_steal_ms is None:
            try:
                affinity_steal_ms = float(
                    os.environ.get("TEMPO_AFFINITY_STEAL_MS", AFFINITY_STEAL_MS))
            except ValueError:
                affinity_steal_ms = AFFINITY_STEAL_MS
        self.affinity_steal_ms = affinity_steal_ms
        self._aff_ring = Ring(InMemoryKV(), AFFINITY_RING_KEY)
        self._aff_descs: dict[str, InstanceDesc] = {}  # member -> tokens
        self._local_member = "local" if n_workers > 0 else None
        # per-tenant read QoS (concurrency / inflight-byte budgets):
        # overrides-driven, so without overrides there is no gate
        self.qos = QueryAdmission(overrides) if overrides is not None else None
        self._remote_workers: dict[str, float] = {}  # worker id -> last poll
        # worker id -> the device it reported with its polls (a querier
        # that owns a chip: services/worker); lease id -> its worker
        self._remote_devices: dict[str, dict] = {}
        # worker id -> the blocks its newest poll said it holds staged
        # columns for (ops/stage.staged_block_ids in the querier)
        self._remote_holds: dict[str, frozenset] = {}
        self._lease_workers: dict[str, str] = {}
        self._lost_at: dict[str, float] = {}  # worker id -> worker_lost()
        # backend-leg circuit breaker (util/breaker): block-scanning
        # jobs shed fast onto the shard-degradation path while the
        # backend is dying, with half-open probes for recovery
        self.backend_breaker = get_breaker("backend")
        # lease id -> ([(tenant, job), ...], expiry, [exec_seq, ...]);
        # a `multi` wire job leases its whole merged batch under one id
        self._leases: dict[str, tuple] = {}
        self._lease_lock = threading.Lock()
        self.stats_jobs_remote = 0
        self.stats_jobs_local = 0
        from ..util.metrics import Histogram

        self.query_latency = Histogram("tempo_frontend_query_duration_seconds")
        self.self_tracer = None  # set by the app when self-tracing is on
        # Tier A result cache, AHEAD of queue admission: a hit answers
        # without touching QoS budgets, the queue, or a device. With
        # TEMPO_RESULT_CACHE=0 no cache object exists at all and every
        # query path below is byte-identical to a cacheless build. The
        # app points live_gen at the local ingester when one exists.
        if _cfg.get_bool("TEMPO_RESULT_CACHE"):
            from .resultcache import ResultCache

            # blocklists without a generation feed (stub queriers in
            # tests, exotic embeddings) get a constant generation: the
            # cache still keys correctly, it just can't observe block
            # churn -- real db.Blocklist always provides one
            bl_gen = getattr(
                getattr(querier.db, "blocklist", None), "generation", None)
            self.result_cache = ResultCache(
                blocklist_gen=bl_gen or (lambda t: 0))
        else:
            self.result_cache = None
        self._workers = [
            threading.Thread(target=self._worker, daemon=True, name=f"frontend-worker-{i}")
            for i in range(n_workers)
        ]
        for w in self._workers:
            w.start()

    def _emit_self_trace(self, jobs: list[_Job], t) -> None:
        """Materialize the per-job spans of the active trace: one span
        per dispatched job at its PRE-ASSIGNED id (engine/remote spans
        already parent onto it), with the enqueue->dequeue wait as a
        child -- the queue-wait leg of the timeline."""
        for j in jobs:
            if not (j.started_wall and j.done_at):
                continue
            attrs = {"cancelled": j.cancelled, "hedged": j.hedged,
                     "error": j.error is not None, **j.span_attrs}
            if j.hedge_outcome:
                attrs["hedge"] = j.hedge_outcome  # win | lose | unneeded
            if j.tries:
                attrs["tries"] = j.tries
            if j.placement:
                attrs["placement"] = j.placement
            sid = t.child(f"job:{j.kind}", j.started_wall, j.done_at, attrs,
                          parent=t.root_id, span_id=j.span_id or None)
            if j.dequeued_wall and j.dequeued_wall >= j.started_wall:
                t.child("queue-wait", j.started_wall, j.dequeued_wall,
                        {}, parent=sid)
            if j.handed_wall and j.handed_wall >= j.started_wall:
                # enqueue -> a worker has it in hand: the queue wait
                # plus, for a remote querier, the poll's way back
                dattrs = {"worker": j.worker, "remote": j.worker != "local",
                          "placement": _PLACEMENT_NAMES.get(j.placement,
                                                            j.placement)}
                if j.placement == "steal":
                    dattrs["warm"] = j.warm
                t.child("job:dispatch", j.started_wall, j.handed_wall,
                        dattrs, parent=sid)
            if j.posted_wall and j.merged_wall >= j.posted_wall:
                t.child("job:result", j.posted_wall, j.merged_wall,
                        {"worker": j.worker}, parent=sid)

    # --------------------------------------------------- affinity routing
    def _affinity_members(self) -> list[InstanceDesc]:
        """The live cache domains jobs can be placed on: this process
        (when it runs local workers -- its threads share one staged
        cache) plus every remote querier that polled within
        worker_expiry_s. Token sets are deterministic per member id, so
        every frontend replica computes the same placement."""
        now = time.monotonic()
        members = [self._local_member] if self._local_member else []
        out = []
        with self._lease_lock:
            remote = [w for w, t in self._remote_workers.items()
                      if now - t < self.worker_expiry_s]
            members += sorted(remote)
            live = set(members)
            for m in list(self._aff_descs):
                if m not in live:  # churned worker ids must not accumulate
                    del self._aff_descs[m]
            for m in list(self._remote_holds):
                if m not in live:  # what a dropped worker held goes with it
                    del self._remote_holds[m]
            for m in members:
                d = self._aff_descs.get(m)
                if d is None:
                    d = self._aff_descs[m] = InstanceDesc(
                        instance_id=m, state=InstanceState.ACTIVE,
                        tokens=deterministic_tokens(AFFINITY_RING_KEY, m))
                out.append(d)
        return out

    def _claimer(self, member: str):
        """Build claim(tenant, job, now) for one dequeue pass by
        `member`, or None when affinity is off or there is at most one
        cache domain (the legacy dequeue, preserved exactly). Ownership
        is resolved within the tenant's REACHABLE domains: with querier
        shuffle-shard on, a block is placed on the shard-member subset
        (plus the local pool, which shuffle-shard never filters), never
        on a worker the tenant's jobs can't be handed to -- otherwise
        every such job would pay the full steal timeout for an owner
        that can never claim it. Lookups memoize per pass -- one ring
        walk per distinct (tenant, block) per dequeue. A non-owner that
        holds staged columns of the job's block (the set its newest poll
        reported; the local pool reads its own process's) steals at
        once: locality is satisfied by any replica, and the steal clock
        is for domains that would have to upload the block. A stale
        "holds it" costs what a blind steal costs (an upload), a stale
        "does not" the clock."""
        if not self.affinity_enabled or not member:
            return None
        members = self._affinity_members()
        if len(members) <= 1:
            return None
        ring = self._aff_ring
        steal_s = self.affinity_steal_ms / 1000.0
        owners: dict[tuple[str, str], str | None] = {}
        shards: dict[str, list[InstanceDesc]] = {}
        if member == self._local_member:
            from ..ops.stage import staged_block_ids

            held = staged_block_ids()
        else:
            with self._lease_lock:
                held = self._remote_holds.get(member, frozenset())

        def shard_members(tenant: str) -> list[InstanceDesc]:
            ms = shards.get(tenant)
            if ms is None:
                ms = shards[tenant] = [
                    d for d in members
                    if d.instance_id == self._local_member
                    or self._tenant_allowed(tenant, d.instance_id)]
            return ms

        def claim(tenant: str, job, now: float) -> str | None:
            key = getattr(job, "affinity_key", None)
            if not key:
                return "unowned"
            ck = (tenant, key)
            if ck in owners:
                owner = owners[ck]
            else:
                owner = owners[ck] = ring.owner_of(
                    key, instances=shard_members(tenant))
            if owner == member:
                return "own"
            if owner is None:
                return "unowned"
            queued = getattr(job, "queued_at", 0.0)
            if queued and now - queued < steal_s:
                if key not in held:
                    return None  # owner's job; steal clock still running
                # a replica holder: no upload to save by waiting (the
                # queue takes the job on this answer, so the mark is set
                # only on a job that is handed out)
                job.warm = True
            return "steal"

        return claim

    def _note_placements(self, jobs: list) -> None:
        """Count dequeue placements (kerneltel affinity counters)."""
        from ..util.kerneltel import TEL

        for j in jobs:
            p = getattr(j, "placement", "")
            if p:
                TEL.record_affinity(p, warm=getattr(j, "warm", False))

    def _note_done(self, job, busy_s: float | None = None) -> None:
        """A worker produced this job's result: the dispatch counters
        and the `job:dispatch` / `job:result` rows of the stages table
        (the spans are emitted with the trace, _emit_self_trace).
        `busy_s`: a local job's share of its `run:<kind>` stage (so the
        worker's busy seconds and the stage's cannot drift); a remote
        job's is hand-off to result."""
        from ..util.kerneltel import TEL

        now = time.time()
        if busy_s is None:
            busy_s = now - (job.handed_wall or now)
        TEL.record_dispatch(job.worker or "local", busy_s)
        if job.handed_wall and job.started_wall:
            TEL.record_stage("job:dispatch", job.handed_wall - job.started_wall)
        if job.posted_wall:
            job.merged_wall = now
            TEL.record_stage("job:result", now - job.posted_wall)

    # ------------------------------------------------------ per-tenant QoS
    def _qos_admit(self, tenant: str, est_bytes: int) -> int:
        """Admit one query against the tenant's QoS budgets; returns the
        byte charge release() must return (0 when no gate is wired).
        Sheds with TooManyRequests (the HTTP layer's 429)."""
        if self.qos is None:
            return 0
        refused = self.qos.try_admit(tenant, est_bytes)
        if refused is not None:
            from ..util.kerneltel import TEL

            TEL.record_shed(tenant, refused)
            raise TooManyRequests(
                f"tenant {tenant} over per-tenant {refused} budget")
        return est_bytes

    def _qos_release(self, tenant: str, est_bytes: int) -> None:
        if self.qos is not None:
            self.qos.release(tenant, est_bytes)

    # ------------------------------------------------------- local workers
    WORKER_DEQUEUE_BATCH = 16  # same-key jobs one worker drains per pull

    def _worker(self):
        while True:
            item = self.queue.dequeue_batch(
                timeout=1.0, max_batch=self.WORKER_DEQUEUE_BATCH,
                key_fn=lambda j: j.batch_key,
                claim=self._claimer(self._local_member or ""))
            if item is None:
                if self.queue.closed:
                    return
                continue
            tenant, job, extras = item
            self._note_placements([job] + [j for _, j in extras])
            if extras and job.batch_fn is not None:
                self._execute_batch([(tenant, job)] + extras)
                continue
            self._execute_one(tenant, job)
            for t2, j2 in extras:  # batch_fn-less jobs never batch
                self._execute_one(t2, j2)

    def _execute_batch(self, group: list) -> None:
        """Run same-key jobs as ONE multi-job call (the coalesced db
        APIs); any failure degrades to per-job execution so a batch is
        never worse than the jobs run singly. Only the lead job's
        self-trace is parked (the fused launch is one device step)."""
        live = []
        for t, j in group:
            if j.cancelled or j.done.is_set():
                j.finish()
            else:
                live.append((t, j))
        if not live:
            return
        from ..util.kerneltel import TEL
        from .selftrace import reset_current_span, set_current_span

        br = self._breaker_for(live[0][1].kind)
        if br is not None and br.state != "closed":
            # open/half-open: run the group per job so breaker probe
            # accounting stays one allow() per call -- a fused batch
            # would ram N block scans through one half-open probe slot
            # (and close the breaker off N records from one grant)
            for t, j in live:
                self._execute_one(t, j)
            return
        now_wall = time.time()
        seqs: dict[int, int] = {}
        for _, j in live:
            if not j.dequeued_wall:
                j.dequeued_wall = now_wall
            j.worker, j.handed_wall = "local", now_wall
            j.exec_seq += 1
            seqs[id(j)] = j.exec_seq
            if j.exec_seq >= 2:
                j.hedge_started = True
        lead = live[0][1]
        token = (TEL.set_active_trace(lead.trace)
                 if lead.trace is not None else None)
        stoken = (set_current_span(lead.span_id)
                  if lead.trace is not None and lead.span_id else None)
        ptoken = TEL.set_affinity_placement(lead.placement)
        results = None
        try:
            with TEL.stage(f"run:{lead.kind}", jobs=len(live)) as run:
                results = lead.batch_fn(live)
        except Exception:
            results = None
        finally:
            TEL.reset_affinity_placement(ptoken)
            if stoken is not None:
                reset_current_span(stoken)
            if token is not None:
                TEL.reset_active_trace(token)
        # window mates rode the lead's fused launch: stamp each mate's
        # OWN trace with a span under its job span naming the lead, so
        # every coalesced query's timeline shows where its device step
        # actually ran (the batch-window propagation contract)
        t1_wall = time.time()
        for _, j in live[1:]:
            if j.trace is not None and j.trace is not lead.trace:
                j.trace.child(
                    "batch:ride", now_wall, t1_wall,
                    {"lead_trace": (lead.trace.trace_id.hex()
                                    if lead.trace is not None else ""),
                     "occupancy": len(live)},
                    parent=j.span_id or None)
        if isinstance(results, list) and len(results) == len(live):
            for (t, j), r in zip(live, results):
                if isinstance(r, Exception):
                    # per-item failure inside the batch: same retry
                    # policy as single execution, isolated to this job
                    if br is not None and _retryable(r):
                        br.record(False)
                    self._fail_job(t, j, r)
                    continue
                if br is not None:
                    br.record(True)
                self._note_result(j, seqs.get(id(j), 1))
                if not j.done.is_set():
                    j.result = r
                self.stats_jobs_local += 1
                self._note_done(j, run.seconds / len(live))
                j.finish()
        else:
            for t, j in live:
                self._execute_one(t, j)

    def _breaker_for(self, kind: str):
        """The backend-leg breaker for block-scanning kinds (lazy: a
        partially-built Frontend -- tests use __new__ -- still gets
        one on first use)."""
        if kind not in BACKEND_KINDS:
            return None
        br = getattr(self, "backend_breaker", None)
        if br is None:
            br = self.backend_breaker = get_breaker("backend")
        return br

    def _grant_retry(self, job) -> bool:
        """One more dispatch for a retryable shard failure? The per-
        query RetryBudget caps TOTAL retries across all of a query's
        jobs, so a dying backend can't amplify one query into a
        jobs x MAX_RETRIES storm."""
        from ..util.kerneltel import TEL

        b = job.retry_budget
        if b is None or b.take():
            TEL.record_retry("retry")
            return True
        TEL.record_retry("budget_exhausted")
        return False

    def _note_result(self, job, seq: int) -> None:
        """Hedge attribution, called by the execution leg that produced
        a result BEFORE publishing it: on the first completion of a
        hedged job, say whether the twin won (seq >= 2), lost (the
        original won after the twin started), or was unneeded (the
        original won before the twin ever ran). A job that also
        RETRIED is left unattributed: retry re-dispatches share the
        exec_seq counter, so a retry completion would masquerade as a
        hedge win exactly in the fault regimes hedging is watched in."""
        if not job.hedged or job.done.is_set() or job.hedge_outcome:
            return
        if job.tries or job.lease_redispatched:
            # retries and lease-expiry redispatches share exec_seq, so
            # their completions would masquerade as hedge wins exactly
            # in the fault regimes this metric is watched in
            return
        from ..util.kerneltel import TEL

        if seq >= 2:
            outcome = "win"
        elif job.exec_seq >= 2 or job.hedge_started:
            outcome = "lose"
        else:
            outcome = "unneeded"
        job.hedge_outcome = outcome
        TEL.record_hedge(outcome)

    def _fail_job(self, tenant: str, job, e: Exception) -> None:
        """Apply the single-job failure policy (transient -> re-enqueue
        up to MAX_RETRIES within the query's retry budget, else error)
        to one job."""
        if job.done.is_set():
            return
        job.tries += 1
        if _retryable(e) and job.tries < MAX_RETRIES and self._grant_retry(job):
            try:
                job.affinity_key = None  # retry dodges the failing owner
                self.queue.enqueue(tenant, job)
                return
            except TooManyRequests:
                pass
        # re-check: a hedge twin may have succeeded while we attempted
        # the re-enqueue -- its result must not be clobbered with an
        # error the waiter would raise
        if not job.done.is_set():
            job.error = e
        job.finish()

    def _execute_one(self, tenant: str, job) -> None:
        if job.cancelled or job.done.is_set():
            job.finish()
            return
        if job.deadline_unix and time.time() > job.deadline_unix:
            # the caller's deadline already passed: don't burn an
            # engine pass nobody can use. Stamp the SAME TimeoutError
            # the dispatch deadline does -- a silently-cancelled shard
            # would let find/metrics return partial results their
            # shard-loss rule forbids
            job.error = TimeoutError("query deadline exceeded before "
                                     "execution")
            job.cancelled = True
            job.finish()
            return
        from ..util.kerneltel import TEL
        from .selftrace import reset_current_span, set_current_span

        br = self._breaker_for(job.kind)
        if br is not None and not br.allow():
            # shed fast onto the shard-degradation path: search merges
            # what the healthy shards return; CircuitOpen is not
            # retryable, so the job never re-enters the open breaker
            job.error = CircuitOpen("backend circuit breaker open")
            job.finish()
            return
        job.exec_seq += 1
        seq = job.exec_seq
        if seq >= 2:
            job.hedge_started = True
        if not job.dequeued_wall:
            job.dequeued_wall = time.time()
        job.worker, job.handed_wall = "local", time.time()
        token = (TEL.set_active_trace(job.trace)
                 if job.trace is not None else None)
        stoken = (set_current_span(job.span_id)
                  if job.trace is not None and job.span_id else None)
        ptoken = TEL.set_affinity_placement(getattr(job, "placement", ""))
        try:
            with TEL.stage(f"run:{job.kind}", jobs=1) as run:
                res = job.fn(*job.args)
            if br is not None:
                br.record(True)
            self._note_result(job, seq)
            if not job.done.is_set():
                job.result = res
            self.stats_jobs_local += 1
            self._note_done(job, run.seconds)
        except Exception as e:
            # retry only transient failures (reference retries 5xx
            # only, modules/frontend/retry.go); a parse error or bad
            # argument fails identically every try. A hedge twin's
            # failure must never clobber its sibling's success.
            # Breaker food is TRANSIENT IO failures only: a device
            # fault / bad query failing a block job says nothing about
            # backend health and must not open the backend leg.
            if br is not None and _retryable(e):
                br.record(False)
            self._fail_job(tenant, job, e)
            return
        finally:
            TEL.reset_affinity_placement(ptoken)
            if stoken is not None:
                reset_current_span(stoken)
            if token is not None:
                TEL.reset_active_trace(token)
        job.finish()

    # -------------------------------------------- coalesced job execution
    def _batch_search_blocks(self, group: list) -> list:
        """Same-key search_blocks jobs -> one multi-request db call (the
        batching executor fuses eligible ones into one launch)."""
        return self.querier.search_blocks_multi(
            [(j.args[0], j.args[1], j.args[2]) for _, j in group])

    def _batch_search_shards(self, group: list) -> list:
        return self.querier.search_block_shard_multi(
            [(j.args[0], j.args[1], j.args[2], j.args[3]) for _, j in group])

    def _batch_find_blocks(self, group: list) -> list:
        return self.querier.find_in_blocks_multi(
            [(j.args[0], j.args[1], j.args[2]) for _, j in group])

    # ------------------------------------------------ remote querier pull
    def _tenant_allowed(self, tenant: str, worker_id: str) -> bool:
        """Per-tenant querier shuffle-shard: with max_queriers_per_tenant
        set, each tenant's jobs go to a deterministic subset of the
        currently-attached workers (user_queues.go). Subsets re-shuffle
        as workers come and go, and every tenant always has at least one
        live assigned worker by construction."""
        if not worker_id or self.overrides is None:
            return True
        k = self.overrides.for_tenant(tenant).max_queriers_per_tenant
        if k <= 0:
            return True
        now = time.monotonic()
        with self._lease_lock:
            self._remote_workers = {
                w: t for w, t in self._remote_workers.items()
                if now - t < self.worker_expiry_s
            }
            workers = sorted(self._remote_workers)
        if k >= len(workers):
            return True
        import random

        from ..util.hashing import fnv1a_32

        rng = random.Random(fnv1a_32(tenant.encode()))
        return worker_id in rng.sample(workers, k)

    REMOTE_BATCH_MAX = 8  # same-key jobs merged into one wire pull

    def poll_job(self, wait_s: float = 5.0, worker_id: str = "",
                 device: dict | None = None, staged_blocks=None):
        """Long-poll dequeue for a remote querier worker
        (frontend_processor.go's stream recv). Returns a wire job dict
        or None on timeout. Same-key jobs queued at poll time merge into
        ONE `multi` wire job (the remote face of the batch-aware
        dequeue), leased together. Expired leases re-enter the queue
        first. Affinity: this worker prefers jobs whose block hashes to
        it on the cache-domain ring; a peer's jobs become claimable only
        past the steal timeout, unless `staged_blocks` -- the block ids
        this querier holds staged columns for, replaced by every poll;
        a poll without it (an older querier) holds nothing -- has the
        job's block. The wire job carries the dequeue placement so the
        remote process attributes its staged-cache hits."""
        began = time.monotonic()
        if worker_id:
            with self._lease_lock:
                self._remote_workers[worker_id] = began
                self._remote_holds[worker_id] = frozenset(
                    staged_blocks if isinstance(staged_blocks, (list, tuple, set, frozenset))
                    else ())
                if device:
                    self._remote_devices[worker_id] = device
        self._requeue_expired()
        allowed = (lambda t: self._tenant_allowed(t, worker_id)) if worker_id else None
        deadline = time.monotonic() + wait_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            item = self.queue.dequeue_batch(
                timeout=min(remaining, 1.0), allowed=allowed,
                max_batch=self.REMOTE_BATCH_MAX,
                key_fn=lambda j: j.batch_key,
                claim=self._claimer(worker_id))
            if item is None:
                if self.queue.closed:
                    return None
                continue
            tenant, job, extras = item
            pairs = []
            for t, j in [(tenant, job)] + list(extras):
                if j.cancelled or j.done.is_set():
                    j.finish()
                elif (j.kind in BACKEND_KINDS
                      and not self._breaker_for(j.kind).allow()):
                    # remote pulls shed at the same breaker as local
                    # workers: an open backend breaker means NOBODY
                    # scans blocks, not just this process
                    j.error = CircuitOpen("backend circuit breaker open")
                    j.finish()
                else:
                    pairs.append((t, j))
            if not pairs:
                continue
            self._note_placements([j for _, j in pairs])
            now_wall = time.time()
            seqs = []
            for _, j in pairs:
                if not j.dequeued_wall:
                    j.dequeued_wall = now_wall
                # (the querier's own stamp replaces this with its result)
                j.worker, j.handed_wall = worker_id or "remote", now_wall
                j.exec_seq += 1
                seqs.append(j.exec_seq)
                if j.exec_seq >= 2:
                    j.hedge_started = True
            jid = uuid.uuid4().hex
            with self._lease_lock:
                self._leases[jid] = (pairs, time.monotonic() + self.lease_s,
                                     seqs)
                self._lease_workers[jid] = worker_id
                orphan = self._lost_at.get(worker_id, 0.0) >= began
            if orphan:
                # the worker died while this poll of its waited here (the
                # handler outlives the socket by up to wait_s): nobody
                # would read the answer, and the lease would hold the job
                # for lease_s -- a hedge twin can meet the same fate
                self._requeue_expired(lost_lease=jid)
                return None
            placement = pairs[0][1].placement
            # deadline propagation, gRPC-style RELATIVE budget: the
            # remaining seconds at dispatch ride the wire job, so the
            # worker's skip decision never depends on clock agreement
            # between the two hosts (an absolute unix deadline would
            # silently shrink -- or zero -- under NTP skew). A merged
            # multi job spans SEVERAL queries, so it carries the MAX:
            # the worker may only skip when every window-mate's caller
            # has given up -- min() would let one expired straggler
            # poison fresh queries merged into its window
            deadlines = [j.deadline_unix for _, j in pairs
                         if j.deadline_unix]
            deadline_in_s = (round(max(deadlines) - time.time(), 3)
                             if deadlines else None)
            # self-trace propagation: the remote leg records its spans
            # against (trace_id, parent=this job's span) and ships them
            # back with the result -- one timeline tree, wherever the
            # leg ran. A multi job rides the LEAD's context (the fused
            # launch is one device step, same as local batch execution).
            lead = pairs[0][1]
            trace_ctx = (lead.trace.wire_context(lead.span_id or None)
                         if lead.trace is not None else None)
            if len(pairs) == 1:
                t0, j0 = pairs[0]
                return {"id": jid, "tenant": t0, "kind": j0.kind,
                        "payload": j0.payload, "placement": placement,
                        "deadline_in_s": deadline_in_s,
                        "trace": trace_ctx}
            return {"id": jid, "tenant": pairs[0][0], "kind": "multi",
                    "placement": placement, "trace": trace_ctx,
                    "deadline_in_s": deadline_in_s,
                    "payload": {"kind": pairs[0][1].kind,
                                "tenants": [t for t, _ in pairs],
                                "jobs": [j.payload for _, j in pairs]}}

    def complete_job(self, jid: str, ok: bool, result: dict | None = None,
                     error: str = "", retryable: bool = False,
                     self_spans: list | None = None,
                     skipped: bool = False, received_unix: float = 0.0,
                     posted_unix: float = 0.0) -> None:
        """Remote worker posts a job result (or a `multi` result list,
        demuxed per leased job). Unknown/expired lease ids are dropped
        (the job was re-dispatched or timed out). self_spans: the remote
        leg's recorded timeline spans, grafted into the lead job's
        trace (they were recorded against its span ids)."""
        with self._lease_lock:
            lease = self._leases.pop(jid, None)
            self._lease_workers.pop(jid, None)
        if lease is None:
            return
        pairs, _, lease_seqs = lease
        if self_spans:
            lead = pairs[0][1]
            if lead.trace is not None:
                lead.trace.add_remote_spans(self_spans)
        # whether this result actually EXERCISED the backend: worker-
        # side deadline skips and (below) undecodable/short results are
        # client/worker faults -- feeding them to the backend breaker
        # would let a backlogged queue or a buggy worker trip it and
        # shed block scans while the object store is perfectly healthy
        backend_exercised = not skipped
        results: list = [result or {}]
        if ok and len(pairs) > 1:
            results = (result or {}).get("results") or []
            if len(results) != len(pairs):
                ok, retryable = False, True
                error = error or "multi result arity mismatch"
                backend_exercised = False
        for i, (tenant, job) in enumerate(pairs):
            if job.done.is_set():
                continue
            job_ok, job_retryable, job_error = ok, retryable, error
            job_exercised = backend_exercised
            # results may be short (worker posted ok=False, or a multi
            # arity mismatch): never index past it -- every leased job
            # must still reach the retry/fail policy below, not hang
            # until the dispatch deadline on an IndexError
            if len(pairs) == 1:
                res_i = results[0]
            else:
                res_i = results[i] if i < len(results) else None
            if job_ok and isinstance(res_i, dict) and "__job_error__" in res_i:
                # per-job failure marker from a multi worker: only THIS
                # job fails/retries, its window-mates keep their results
                job_ok = False
                job_retryable = bool(res_i.get("__retryable__"))
                job_error = str(res_i["__job_error__"])
            elif job_ok:
                try:
                    decoded = decode_job_result(job.kind, res_i)
                except Exception as e:  # malformed result from a buggy
                    # worker: treat as a retryable failure so the request
                    # doesn't hang with its lease already popped
                    job_ok, job_retryable = False, True
                    job_error = f"undecodable result: {e}"
                    job_exercised = False  # worker bug, not a backend one
                else:
                    self._note_result(
                        job, lease_seqs[i] if i < len(lease_seqs) else 1)
                    job.result = decoded
                    self.stats_jobs_remote += 1
                    # the querier's clock is this host's: its stamps of
                    # taking the job and of posting the result bound
                    # the two crossings of the wire
                    if received_unix:
                        job.handed_wall = max(received_unix, job.dequeued_wall)
                    job.posted_wall = posted_unix
                    self._note_done(job)
            # breaker food is results that exercised the backend AND
            # (on failure) look transient -- deterministic failures
            # (bad query, missing object) say nothing about its health
            if job_exercised and (job_ok or job_retryable):
                br = self._breaker_for(job.kind)
                if br is not None:
                    br.record(job_ok)
            if not job_ok:
                job.tries += 1
                if (job_retryable and job.tries < MAX_RETRIES
                        and self._grant_retry(job)):
                    try:
                        # demote to placement-free: a sick-but-alive
                        # owner polls fastest right after failing and
                        # would win its own job back every retry inside
                        # the steal window
                        job.affinity_key = None
                        self.queue.enqueue(tenant, job)
                        continue
                    except TooManyRequests:
                        pass
                job.error = RuntimeError(job_error or "remote job failed")
            job.finish()

    def attached_workers(self) -> dict[str, dict]:
        """Remote queriers that polled within worker_expiry_s and said
        which device they own: worker id -> {platform, device_kind,
        count}."""
        now = time.monotonic()
        with self._lease_lock:
            return {w: dict(self._remote_devices[w])
                    for w, t in self._remote_workers.items()
                    if now - t < self.worker_expiry_s
                    and w in self._remote_devices}

    def worker_lost(self, worker_id: str) -> None:
        """A querier is known to be gone (its supervisor saw it exit):
        forget it as a cache domain and put what it had leased back on
        the queue now instead of at the lease's end."""
        with self._lease_lock:
            self._remote_workers.pop(worker_id, None)
            self._remote_devices.pop(worker_id, None)
            self._remote_holds.pop(worker_id, None)
            self._lost_at[worker_id] = time.monotonic()
        self._requeue_expired(lost_worker=worker_id)

    def _requeue_expired(self, lost_worker: str = "", lost_lease: str = "") -> None:
        now = time.monotonic()
        expired = []
        with self._lease_lock:
            for jid, (pairs, exp, _seqs) in list(self._leases.items()):
                if exp < now or jid == lost_lease or (
                        lost_worker and
                        self._lease_workers.get(jid) == lost_worker):
                    expired.extend(pairs)
                    del self._leases[jid]
                    self._lease_workers.pop(jid, None)
        for tenant, job in expired:
            if not (job.done.is_set() or job.cancelled):
                try:
                    job.lease_redispatched = True
                    self.queue.enqueue(tenant, job)
                except TooManyRequests:
                    job.error = TimeoutError("job lease expired, queue full")
                    job.finish()

    # ---------------------------------------------------------- dispatch
    @staticmethod
    def _retry_budget_total(n_jobs: int) -> int:
        """Per-query retry cap: enough to absorb transient faults on a
        few shards, sublinear in fan-out so a dying backend sees
        additive (not multiplicative) retry load. TEMPO_RETRY_BUDGET
        overrides."""
        try:
            env = int(os.environ.get("TEMPO_RETRY_BUDGET", "") or 0)
        except ValueError:
            env = 0
        return env if env > 0 else max(4, n_jobs // 4)

    def _run_jobs(self, tenant: str, jobs: list[_Job], early_exit=None,
                  timeout: float = 60.0) -> None:
        """Enqueue with bounded in-flight jobs, reap completions in ANY
        order (one slow shard no longer stalls dispatch), hedge jobs a
        worker has had in hand for hedge_after_s where another worker
        could take the twin, and cancel everything at the deadline
        so late workers see job.cancelled and skip. Every job shares
        one RetryBudget (total retries per QUERY, not per job) and
        carries the wall-clock deadline so remote workers skip jobs
        whose caller already gave up."""
        cv = threading.Condition()
        budget = RetryBudget(self._retry_budget_total(len(jobs)))
        deadline_unix = time.time() + timeout
        for j in jobs:
            j.batch_cv = cv
            j.retry_budget = budget
            j.deadline_unix = deadline_unix
        deadline = time.monotonic() + timeout
        pending = list(jobs)
        inflight: list[_Job] = []
        while pending or inflight:
            if early_exit is not None and early_exit():
                for j in pending:
                    j.cancelled = True
                    j.finish()
                pending = []
            while pending and len(inflight) < self.concurrent_jobs:
                j = pending.pop(0)
                j.started_wall = time.time()
                self.queue.enqueue(tenant, j)
                inflight.append(j)
            inflight = [j for j in inflight if not j.done.is_set()]
            if not inflight and not pending:
                break
            now = time.monotonic()
            if now >= deadline:
                for j in inflight + pending:
                    j.error = TimeoutError("query job timed out")
                    j.cancelled = True
                    j.finish()  # stamps done_at: the slow job must show
                    # up in self-traces -- it IS the pathology
                break
            # a job some worker has had in hand for hedge_after_s gets a
            # twin if a live cache domain other than that worker's could
            # take it. The single binary has one domain and never hedges:
            # a twin there shares the original's interpreter, stage lock
            # and chip, and a job that is slow because the process is busy
            # only gets slower
            late = time.time() - self.hedge_after_s
            overdue = [j for j in inflight if not j.hedged
                       and 0 < j.handed_wall < late] if self.hedge_after_s > 0 else []
            if overdue:
                domains = {d.instance_id for d in self._affinity_members()}
                for j in overdue:
                    if domains - {j.worker}:
                        j.hedged = True  # re-enqueue; first completion wins
                        try:
                            self.queue.enqueue(tenant, j)
                        except TooManyRequests:
                            pass
            with cv:
                cv.wait(min(0.25, deadline - now))

    # ----------------------------------------------------------- trace by id
    def find_trace_by_id(self, tenant: str, trace_id: bytes,
                         time_start: int = 0, time_end: int = 0):
        """ID-sharded lookup: one ingester-leg job plus the candidate
        blocks partitioned into parallel backend jobs, partial traces
        combined (tracebyidsharding.go:30-48 splits the ID space; here
        the candidate block set IS the shardable space, since the device
        engine answers a whole partition in one batched lookup)."""
        from ..util.kerneltel import TEL

        t0 = time.perf_counter()
        self_tid = ""
        outcome = "ok"
        try:
            if self.self_tracer is None or tenant == self.self_tracer.tenant:
                return self._find_trace_by_id(tenant, trace_id, time_start, time_end)
            with self.self_tracer.trace(
                "frontend.find_trace_by_id", {"tenant": tenant}
            ) as t:
                self_tid = t.trace_id.hex()
                return self._find_trace_by_id(tenant, trace_id, time_start, time_end,
                                              trace=t)
        except TooManyRequests:
            outcome = "shed"  # QoS budget refusal, not a serving failure
            raise
        except Exception:
            outcome = "error"
            raise
        finally:
            dt = time.perf_counter() - t0
            # exemplar: the latency histogram links to the self-trace
            self.query_latency.observe(dt, 'op="traces"',
                                       exemplar=self_tid or None)
            TEL.record_query("traces", dt, self_tid, trace_id.hex(),
                             outcome=outcome)

    def _qos_admit_traced(self, tenant: str, est_bytes: int, trace) -> int:
        """_qos_admit with a timeline span when a trace is active (the
        QoS admission leg; a shed shows as error=true on the root)."""
        if trace is None or self.qos is None:
            return self._qos_admit(tenant, est_bytes)
        t0 = time.time()
        try:
            return self._qos_admit(tenant, est_bytes)
        finally:
            trace.child("qos-admit", t0, time.time(),
                        {"est_bytes": int(est_bytes)})

    def _find_trace_by_id(self, tenant: str, trace_id: bytes,
                          time_start: int = 0, time_end: int = 0, trace=None):
        rc = self.result_cache
        if rc is None:
            return self._find_trace_exec(tenant, trace_id, time_start,
                                         time_end, trace)
        hex_id = trace_id.hex()
        tr = rc.probe_trace(tenant, hex_id, time_start, time_end)
        if tr is not None:
            return tr
        tr = self._find_trace_exec(tenant, trace_id, time_start, time_end, trace)
        if tr is not None:
            # sized by span count (a serialization pass per store would
            # cost more than the lookup it saves); ~1KiB/span wire-side
            rc.store_trace(tenant, hex_id, time_start, time_end, tr,
                           nbytes=max(1024, tr.span_count() * 1024))
        return tr

    def _find_trace_exec(self, tenant: str, trace_id: bytes,
                         time_start: int = 0, time_end: int = 0, trace=None):
        db = self.querier.db
        candidates = db.find_candidates(tenant, trace_id, time_start, time_end)
        charge = self._qos_admit_traced(
            tenant, sum(m.size_bytes or 0 for m in candidates), trace)
        try:
            jobs = [_Job(
                kind="find_recent",
                payload={"trace_id": trace_id.hex()},
                fn=self.querier.find_trace_by_id,
                args=(tenant, trace_id, time_start, time_end, True, False),
            )]
            for i in range(0, len(candidates), FIND_SHARD_BLOCKS):
                part = candidates[i : i + FIND_SHARD_BLOCKS]
                jobs.append(_Job(
                    kind="find_blocks",
                    payload={"trace_id": trace_id.hex(),
                             "block_ids": [m.block_id for m in part]},
                    fn=self.querier.find_in_blocks,
                    args=(tenant, trace_id, part),
                    batch_key=("find_blocks", tenant,
                               tuple(m.block_id for m in part)),
                    batch_fn=self._batch_find_blocks,
                    affinity_key=part[0].block_id,
                ))
            attach_trace(jobs, trace)
            self._run_jobs(tenant, jobs)
        finally:
            self._qos_release(tenant, charge)
        if trace is not None:
            self._emit_self_trace(jobs, trace)
        partials = []
        for j in jobs:
            if j.error is not None:
                # a failed shard means the combined trace could silently
                # miss spans: fail the request (reference behavior)
                raise j.error
            if j.result is not None:
                partials.append(j.result)
        if not partials:
            return None
        return sort_trace(combine_traces(partials)) if len(partials) > 1 else partials[0]

    # ---------------------------------------------------------------- search
    def search(self, tenant: str, req: SearchRequest) -> SearchResponse:
        """Sharded search: ingester job + block-batch jobs (+ row-group
        shard jobs for oversized blocks), bounded concurrency, early
        exit at limit."""
        from ..util.kerneltel import TEL

        t0 = time.perf_counter()
        self_tid = ""
        outcome = "ok"
        try:
            if self.self_tracer is None or tenant == self.self_tracer.tenant:
                return self._search(tenant, req)
            with self.self_tracer.trace(
                "frontend.search", {"tenant": tenant, "q": req.query or ""}
            ) as t:
                self_tid = t.trace_id.hex()
                return self._search(tenant, req, trace=t)
        except TooManyRequests:
            outcome = "shed"
            raise
        except Exception:
            outcome = "error"
            raise
        finally:
            dt = time.perf_counter() - t0
            self.query_latency.observe(dt, 'op="search"',
                                       exemplar=self_tid or None)
            TEL.record_query("search", dt, self_tid,
                             req.query or " ".join(
                                 f"{k}={v}" for k, v in req.tags.items()),
                             outcome=outcome)

    def _build_search_jobs(self, tenant: str, req: SearchRequest,
                           req_d: dict, metas: list) -> list[_Job]:
        """The search shard plan: one ingester-leg job FIRST (the
        newest data -- streaming delivery leans on this ordering), then
        block-batch jobs (+ row-group shard jobs for oversized
        blocks)."""
        jobs: list[_Job] = [_Job(
            kind="search_recent", payload={"req": req_d},
            fn=self.querier.search_recent, args=(tenant, req),
        )]
        batch: list = []
        batch_bytes = 0

        def flush_batch():
            nonlocal batch, batch_bytes
            if batch:
                part = batch
                jobs.append(_Job(
                    kind="search_blocks",
                    payload={"req": req_d, "block_ids": [m.block_id for m in part]},
                    fn=self.querier.search_blocks, args=(tenant, part, req),
                    batch_key=("search_blocks", tenant,
                               tuple(m.block_id for m in part)),
                    batch_fn=self._batch_search_blocks,
                    affinity_key=part[0].block_id,
                    span_attrs={"blocks": len(part), "bytes": batch_bytes},
                ))
                batch, batch_bytes = [], 0

        for m in metas:
            size = m.size_bytes or 0
            if size > self.batch_bytes:
                # a single oversized block: shard it by row-group range
                for groups in self._group_chunks(m):
                    jobs.append(_Job(
                        kind="search_block_shard",
                        payload={"req": req_d, "block_id": m.block_id, "groups": groups},
                        fn=self.querier.search_block_shard, args=(tenant, m, req, groups),
                        batch_key=("search_block_shard", tenant, m.block_id,
                                   tuple(groups)),
                        batch_fn=self._batch_search_shards,
                        affinity_key=m.block_id,
                    ))
                continue
            if batch_bytes + size > self.batch_bytes or len(batch) >= MAX_BLOCKS_PER_BATCH:
                flush_batch()
            batch.append(m)
            batch_bytes += size
        flush_batch()
        from ..util.kerneltel import TEL

        TEL.record_range(len(metas), [j.span_attrs.get("blocks", 1) for j in jobs[1:]])
        return jobs

    def _search(self, tenant: str, req: SearchRequest, trace=None) -> SearchResponse:
        rc = self.result_cache
        if rc is None:
            return self._search_exec(tenant, req, trace)
        out = rc.probe_search(tenant, req)
        if isinstance(out, SearchResponse):
            return out  # exact hit: no QoS charge, no jobs, no device
        if out is not None:
            # incremental extension: execute ONLY the mutable tail
            # slice through the normal shard plan, merge with the
            # cached immutable prefix
            tail = self._search_exec(tenant, out.tail_req, trace)
            return rc.complete_search_extension(out, tail)
        resp = self._search_exec(tenant, req, trace)
        rc.store_search(tenant, req, resp)
        return resp

    def _search_exec(self, tenant: str, req: SearchRequest,
                     trace=None) -> SearchResponse:
        from ..util.kerneltel import TEL

        limit = req.limit or 20
        resp = SearchResponse()
        lock = threading.Lock()
        req_d = request_to_dict(req)

        metas = [
            m for m in self.querier.db.blocklist.metas(tenant)
            if m.overlaps_time(req.start, req.end)
        ]
        charge = self._qos_admit_traced(
            tenant, sum(m.size_bytes or 0 for m in metas), trace)
        try:
            jobs = self._build_search_jobs(tenant, req, req_d, metas)
            attach_trace(jobs, trace)

            def early():
                with lock:
                    return len(resp.traces) >= limit

            # collect results as jobs complete, merging under the limit
            collector_done = threading.Event()

            def collect():
                t0_merge = time.time()
                token = TEL.set_active_trace(trace)  # this thread's stages
                for j in jobs:
                    j.done.wait()
                    if j.error is None and j.result is not None:
                        with TEL.stage("search:merge", jobs=len(jobs)), lock:
                            resp.merge(j.result, limit)
                TEL.reset_active_trace(token)
                if trace is not None:
                    # the cross-shard merge leg of the timeline
                    trace.child("merge", t0_merge, time.time(),
                                {"jobs": len(jobs)})
                collector_done.set()

            t = threading.Thread(target=collect, daemon=True)
            t.start()
            self._run_jobs(tenant, jobs, early_exit=early)
            collector_done.wait(timeout=60.0)
        finally:
            self._qos_release(tenant, charge)
        if trace is not None:
            self._emit_self_trace(jobs, trace)
            trace.add_cost("bytes_scanned", sum(
                j.result.inspected_bytes for j in jobs
                if j.error is None and j.result is not None))
        token = TEL.set_active_trace(trace)
        try:
            with TEL.stage("search:merge", jobs=len(jobs),
                           cancelled=sum(j.cancelled for j in jobs)):
                resp.traces.sort(key=lambda r: -r.start_time_unix_nano)
                resp.traces = resp.traces[:limit]
        finally:
            TEL.reset_active_trace(token)
        return resp

    # ------------------------------------------------- progressive search
    def search_stream(self, tenant: str, req: SearchRequest):
        """Progressive search: a generator of result snapshots, one per
        completed shard wave, newest-first. Each yield is a dict
        {"traces": [...], "metrics": {...}, "done": bool,
        "jobsCompleted": n, "jobsTotal": m}; the final item has
        done=True and is the exact /api/search response body. Jobs ride
        the SAME queue/lease plane as blocking search -- local workers
        and remote querier polls both complete them -- the frontend just
        flushes the merged snapshot to the client as each completes
        instead of holding everything until the slowest shard."""
        from ..util.kerneltel import TEL
        from ..util.metrics import timed

        t0 = time.perf_counter()
        outcome = "ok"
        try:
            # its OWN query class: progressive delivery has a different
            # latency contract (time-to-final spans the slowest shard
            # by design), so the SLO layer must not fold it into the
            # blocking-search p99
            with timed(self.query_latency, 'op="search_stream"'):
                yield from self._search_stream(tenant, req)
        except TooManyRequests:
            outcome = "shed"
            raise
        except GeneratorExit:
            outcome = "cancelled"  # client went away, not a failure
            raise
        except Exception:
            outcome = "error"
            raise
        finally:
            TEL.record_query("search_stream", time.perf_counter() - t0, "",
                             req.query or " ".join(
                                 f"{k}={v}" for k, v in req.tags.items()),
                             outcome=outcome)

    @staticmethod
    def _stream_final_body(resp: SearchResponse, limit: int) -> dict:
        return {
            "traces": [t.to_dict() for t in resp.traces[:limit]],
            "metrics": {
                "inspectedBytes": str(resp.inspected_bytes),
                "inspectedSpans": str(resp.inspected_spans),
            },
            "done": True,
            "jobsCompleted": 0,  # served from cache: no jobs dispatched
            "jobsTotal": 0,
        }

    def _search_stream(self, tenant: str, req: SearchRequest):
        limit = req.limit or 20
        rc = self.result_cache
        if rc is not None:
            out = rc.probe_search(tenant, req)
            if isinstance(out, SearchResponse):
                # progressive delivery collapses to its final event --
                # the cached response IS the exact /api/search body
                yield self._stream_final_body(out, limit)
                return
            if out is not None:
                tail = self._search_exec(tenant, out.tail_req)
                yield self._stream_final_body(
                    rc.complete_search_extension(out, tail), limit)
                return
        req_d = request_to_dict(req)
        metas = [
            m for m in self.querier.db.blocklist.metas(tenant)
            if m.overlaps_time(req.start, req.end)
        ]
        charge = self._qos_admit(tenant, sum(m.size_bytes or 0 for m in metas))
        runner = None
        jobs: list[_Job] = []
        try:
            jobs = self._build_search_jobs(tenant, req, req_d, metas)
            cv = threading.Condition()
            for j in jobs:
                j.stream_cv = cv
            resp = SearchResponse()
            lock = threading.Lock()

            def early():
                with lock:
                    return len(resp.traces) >= limit

            runner = threading.Thread(
                target=self._run_jobs, args=(tenant, jobs),
                kwargs={"early_exit": early}, daemon=True,
                name="search-stream-dispatch")
            runner.start()

            def body(done: bool) -> dict:
                with lock:
                    traces = sorted(resp.traces,
                                    key=lambda r: -r.start_time_unix_nano)
                    return {
                        "traces": [t.to_dict() for t in traces[:limit]],
                        "metrics": {
                            "inspectedBytes": str(resp.inspected_bytes),
                            "inspectedSpans": str(resp.inspected_spans),
                        },
                        "done": done,
                        "jobsCompleted": len(reaped),
                        "jobsTotal": len(jobs),
                    }

            reaped: set[int] = set()
            while len(reaped) < len(jobs):
                with cv:
                    if not any(j.done.is_set() and id(j) not in reaped
                               for j in jobs):
                        cv.wait(0.25)
                fresh = False
                for j in jobs:
                    if id(j) in reaped or not j.done.is_set():
                        continue
                    reaped.add(id(j))
                    # same tolerance as blocking search: a failed shard
                    # degrades coverage, it doesn't fail the stream
                    if j.error is None and j.result is not None:
                        with lock:
                            n0 = len(resp.traces)
                            resp.merge(j.result, limit)
                            fresh = fresh or len(resp.traces) > n0
                if fresh and len(reaped) < len(jobs):
                    yield body(False)
            runner.join(timeout=5.0)
            runner = None
            with lock:
                resp.traces.sort(key=lambda r: -r.start_time_unix_nano)
                resp.traces = resp.traces[:limit]
            if rc is not None:
                rc.store_search(tenant, req, resp)  # blocking search shares keys
            yield body(True)
        finally:
            if runner is not None:
                # client went away mid-stream: cancel the orphaned jobs
                # FIRST (workers skip cancelled jobs, finish() unblocks
                # the dispatcher), then settle the dispatcher, and only
                # then return the byte charge -- releasing while shard
                # jobs still run would let the tenant exceed its budget
                for j in jobs:
                    if not j.done.is_set():
                        j.cancelled = True
                        j.finish()
                runner.join(timeout=5.0)
            self._qos_release(tenant, charge)

    # ------------------------------------------------------------ metrics
    METRICS_BUCKETS_PER_JOB = 64  # time-shard unit of /api/metrics/query_range

    def metrics_query_range(self, tenant: str, req):
        """Time-sharded metrics range query: the step-aligned bucket
        axis splits into sub-range jobs (the metrics analog of the
        reference's searchsharding time splits), each executed by a
        local worker or a remote querier pull, partial series merged by
        label -- alignment to one global grid makes the shard merge
        exact (metrics_exec.align_params)."""
        from ..util.kerneltel import TEL

        t0 = time.perf_counter()
        self_tid = ""
        outcome = "ok"
        try:
            if self.self_tracer is None or tenant == self.self_tracer.tenant:
                return self._metrics_query_range(tenant, req)
            with self.self_tracer.trace(
                "frontend.metrics_query_range", {"tenant": tenant, "q": req.query}
            ) as t:
                self_tid = t.trace_id.hex()
                return self._metrics_query_range(tenant, req, trace=t)
        except TooManyRequests:
            outcome = "shed"
            raise
        except Exception:
            outcome = "error"
            raise
        finally:
            dt = time.perf_counter() - t0
            self.query_latency.observe(dt, 'op="metrics"',
                                       exemplar=self_tid or None)
            TEL.record_query("metrics", dt, self_tid, req.query,
                             outcome=outcome)

    def _metrics_query_range(self, tenant: str, req, trace=None):
        rc = self.result_cache
        if rc is None:
            return self._metrics_exec(tenant, req, trace)
        out = rc.probe_metrics(tenant, req)
        if out is None:
            resp = self._metrics_exec(tenant, req, trace)
            rc.store_metrics(tenant, req, resp)
            return resp
        from .resultcache import MetricsExtension

        if isinstance(out, MetricsExtension):
            # re-execute only the tail buckets; the prefix accumulator
            # states merge exactly like the time-shard jobs below
            tail = self._metrics_exec(tenant, out.tail_req, trace)
            return rc.complete_metrics_extension(out, tail)
        return out  # exact hit

    def _metrics_exec(self, tenant: str, req, trace=None):
        from ..db.metrics_exec import (
            MetricsRequest,
            MetricsResponse,
            expr_label,
            parse_metrics_query,
            request_to_dict as metrics_request_to_dict,
        )

        q = parse_metrics_query(req.query)  # ParseError -> 400 at the API
        charge = self._qos_admit_traced(tenant, 0, trace)  # concurrency only
        try:
            nb = req.n_buckets
            n_jobs = max(1, -(-nb // self.METRICS_BUCKETS_PER_JOB))
            if nb >= 2 and n_jobs < 2:
                n_jobs = 2  # the shard/merge path is the production path: keep it hot
            per_job = -(-nb // n_jobs)
            jobs: list[_Job] = []
            try:
                metas = self.querier.db.blocklist.metas(tenant)
            except AttributeError:  # a stub querier (tests)
                metas = []
            for lo in range(0, nb, per_job):
                hi = min(lo + per_job, nb)
                sub = MetricsRequest(
                    query=req.query,
                    start_ms=req.start_ms + lo * req.step_ms,
                    end_ms=req.start_ms + hi * req.step_ms,
                    step_ms=req.step_ms,
                )
                # placed like a search of the first block the sub-range
                # covers: whole-block columns then stage where that
                # block's other columns already live
                over = [m.block_id for m in metas if m.overlaps_time(
                    sub.start_ms // 1000, -(-sub.end_ms // 1000))]
                jobs.append(_Job(
                    kind="metrics_query_range",
                    payload={"req": metrics_request_to_dict(sub)},
                    fn=self.querier.metrics_query_range, args=(tenant, sub),
                    affinity_key=over[0] if over else None,
                    span_attrs={"blocks": len(over)},
                ))
            from ..util.kerneltel import TEL

            TEL.record_range(
                sum(m.overlaps_time(req.start_ms // 1000, -(-req.end_ms // 1000))
                    for m in metas),
                [j.span_attrs["blocks"] for j in jobs])
            attach_trace(jobs, trace)
            self._run_jobs(tenant, jobs)
        finally:
            self._qos_release(tenant, charge)
        if trace is not None:
            self._emit_self_trace(jobs, trace)
        resp = MetricsResponse(
            fn=q.agg.fn, start_ms=req.start_ms, step_ms=req.step_ms,
            n_buckets=nb,
            label_names=tuple(expr_label(e, i) for i, e in enumerate(q.agg.by)),
        )
        for j in jobs:
            if j.error is not None:
                # a lost time shard would silently zero part of every
                # series: fail the request (same rule as find shards)
                raise j.error
            if j.result is not None:
                resp.merge(j.result)
        return resp

    def _group_chunks(self, meta) -> list[list[int]]:
        """Split an oversized block's row groups into jobs of
        ~batch_bytes (searchsharding.go:266-310 page-range jobs)."""
        n_groups = max(1, len(meta.row_groups) or 1)
        size = meta.size_bytes or 0
        per_group = max(1, size // n_groups)
        per_job = max(1, int(self.batch_bytes // per_group))
        return [list(range(i, min(i + per_job, n_groups))) for i in range(0, n_groups, per_job)]

    def stop(self):
        self.queue.close()
