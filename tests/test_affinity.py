"""Cache-affinity scheduling + per-tenant read QoS (services/frontend).

Block->querier affinity: jobs hash their lead block onto the cache-
domain ring and the dequeue prefers the owner, with a bounded steal
timeout so a dead owner never strands work. QoS: overrides-driven
per-tenant concurrency/byte budgets shed with 429. Both layers must
vanish exactly when disabled: affinity off (or one cache domain) is the
legacy head-of-queue dequeue, no overrides means no admission gate.
"""

import threading
import time
from dataclasses import replace
from types import SimpleNamespace

import pytest

from tempo_tpu.db.search import SearchRequest, SearchResponse
from tempo_tpu.services.frontend import (
    Frontend,
    RequestQueue,
    TooManyRequests,
    _Job,
)
from tempo_tpu.services.overrides import Limits, Overrides, QueryAdmission
from tempo_tpu.util.kerneltel import TEL

TENANT = "t-aff"


def _job(kind="search_blocks", key=None, batch_key=None, fn=None):
    return _Job(kind=kind, payload={}, fn=fn or (lambda: None), args=(),
                affinity_key=key, batch_key=batch_key)


class _StubQuerier:
    """Just enough querier for Frontend.search's search_recent leg:
    an empty blocklist and a configurable-latency live search."""

    def __init__(self, delay: float = 0.0):
        self.delay = delay
        self.db = SimpleNamespace(
            blocklist=SimpleNamespace(metas=lambda tenant: []))

    def search_recent(self, tenant, req):
        if self.delay:
            time.sleep(self.delay)
        return SearchResponse()


def _dispatcher(**kw) -> Frontend:
    """Dispatcher-only frontend (no local workers): remote queriers are
    the only cache domains, exactly the multi-chip fleet shape."""
    kw.setdefault("n_workers", 0)
    kw.setdefault("affinity", True)
    return Frontend(_StubQuerier(), **kw)


def _attach(fe: Frontend, *workers: str) -> None:
    for w in workers:
        assert fe.poll_job(wait_s=0.01, worker_id=w) is None


def _owner_of(fe: Frontend, key: str) -> str:
    return fe._aff_ring.owner_of(key, instances=fe._affinity_members())


def _keys_by_owner(fe: Frontend, workers, n=64) -> dict:
    """A block id owned by each worker (the ring is deterministic, so
    scan candidate ids until every worker has one)."""
    out = {}
    for i in range(n):
        k = f"block-{i:04x}"
        o = _owner_of(fe, k)
        if o in workers and o not in out:
            out[o] = k
        if len(out) == len(workers):
            return out
    raise AssertionError("no key found for some worker")


# ------------------------------------------------------ queue-level claim


def test_queue_claim_owner_and_unowned_immediate():
    """A claimer takes its own jobs and placement-free jobs at once;
    a peer's job is deferred while the steal clock runs."""
    q = RequestQueue()
    mine, theirs, free = _job(key="b-mine"), _job(key="b-theirs"), _job()
    for j in (theirs, mine, free):
        q.enqueue(TENANT, j)

    def claim(tenant, job, now):
        if job.affinity_key is None:
            return "unowned"
        if job.affinity_key == "b-mine":
            return "own"
        return None  # peer's, clock running

    got = []
    for _ in range(2):
        item = q.dequeue(timeout=0.2, claim=claim)
        assert item is not None
        got.append(item[1])
    assert got == [mine, free]  # FIFO among claimable, peer's skipped
    assert mine.placement == "own" and free.placement == "unowned"
    # only the deferred job remains; this claimer cannot take it yet
    assert q.dequeue(timeout=0.05, claim=claim) is None
    assert theirs.placement == ""


def test_queue_claim_steal_after_timeout():
    """The steal clock is the job's queue age: once it expires the same
    claim call flips from defer to steal, without a fresh enqueue."""
    q = RequestQueue()
    j = _job(key="b-other")
    q.enqueue(TENANT, j)
    steal_s = 0.08

    def claim(tenant, job, now):
        age = now - job.queued_at
        return "steal" if age >= steal_s else None

    t0 = time.monotonic()
    item = q.dequeue(timeout=2.0, claim=claim)
    waited = time.monotonic() - t0
    assert item is not None and item[1] is j
    assert j.placement == "steal"
    # the dequeue's periodic re-check fired the clock, not a notify
    assert steal_s <= waited < 1.0


def test_queue_claim_none_is_legacy_fifo():
    """claim=None must be byte-for-byte the legacy dequeue: strict FIFO
    within a tenant, affinity metadata ignored."""
    q = RequestQueue()
    jobs = [_job(key=f"b{i}") for i in range(4)]
    for j in jobs:
        q.enqueue(TENANT, j)
    out = [q.dequeue(timeout=0.1)[1] for _ in range(4)]
    assert out == jobs
    assert all(j.placement == "" for j in jobs)


def test_queue_batch_extras_ride_lead_claim():
    """Same-coalesce-key window mates join the lead's claim wherever
    they sit in the scan window (same blocks -> same owner), and carry
    the lead's placement."""
    q = RequestQueue()
    bk = ("search_blocks", TENANT, ("blk",))
    lead = _job(key="blk", batch_key=bk)
    other = _job(key="peer-blk", batch_key=("search_blocks", TENANT, ("p",)))
    mate = _job(key="blk", batch_key=bk)
    for j in (lead, other, mate):
        q.enqueue(TENANT, j)

    def claim(tenant, job, now):
        return "own" if job.affinity_key == "blk" else None

    tenant, got, extras = q.dequeue_batch(
        timeout=0.2, max_batch=4, key_fn=lambda j: j.batch_key, claim=claim)
    assert got is lead and [j for _, j in extras] == [mate]
    assert mate.placement == lead.placement == "own"
    # the peer-owned job was skipped over, not consumed
    assert q.dequeue(timeout=0.05) is not None


# -------------------------------------------------- frontend-level routing


def test_frontend_owner_preferred_and_wire_placement():
    """Each attached worker is handed its ring-owned jobs first, and the
    wire job carries the placement for remote staged-cache attribution."""
    fe = _dispatcher(affinity_steal_ms=10_000.0)
    try:
        _attach(fe, "w1", "w2")
        keys = _keys_by_owner(fe, {"w1", "w2"})
        fe.queue.enqueue(TENANT, _job(key=keys["w2"]))
        fe.queue.enqueue(TENANT, _job(key=keys["w1"]))
        # w1 skips w2's (older!) job and takes its own
        wire = fe.poll_job(wait_s=0.5, worker_id="w1")
        assert wire is not None and wire["placement"] == "own"
        wire2 = fe.poll_job(wait_s=0.5, worker_id="w2")
        assert wire2 is not None and wire2["placement"] == "own"
    finally:
        fe.stop()


def test_frontend_single_domain_is_legacy():
    """With one attached worker there is nothing to route between: the
    claimer is None and jobs flow strictly FIFO with no placement."""
    fe = _dispatcher()
    try:
        _attach(fe, "only")
        assert fe._claimer("only") is None
        fe.queue.enqueue(TENANT, _job(key="whatever"))
        wire = fe.poll_job(wait_s=0.5, worker_id="only")
        assert wire is not None and wire["placement"] == ""
    finally:
        fe.stop()


def test_frontend_affinity_off_is_legacy():
    fe = _dispatcher(affinity=False)
    try:
        _attach(fe, "w1", "w2")
        assert fe._claimer("w1") is None and fe._claimer("w2") is None
    finally:
        fe.stop()


def test_affinity_respects_querier_shuffle_shard():
    """With max_queriers_per_tenant=1 ownership is resolved within the
    tenant's one-worker shard: every job is that worker's "own"
    immediately -- a fleet-wide owner outside the shard must never make
    shard members wait out the steal timeout for a worker that cannot
    take the job."""
    ov = Overrides()
    ov.defaults = replace(ov.defaults, max_queriers_per_tenant=1)
    fe = _dispatcher(overrides=ov, affinity_steal_ms=60_000.0)
    try:
        _attach(fe, "w1", "w2")
        keys = _keys_by_owner(fe, {"w1", "w2"})
        # one job per fleet-wide owner: whichever worker the tenant's
        # shard picked must claim BOTH as "own", instantly
        fe.queue.enqueue(TENANT, _job(key=keys["w1"]))
        fe.queue.enqueue(TENANT, _job(key=keys["w2"]))
        got = []
        t0 = time.monotonic()
        for _ in range(4):
            for w in ("w1", "w2"):
                wire = fe.poll_job(wait_s=0.05, worker_id=w)
                if wire:
                    got.append((w, wire["placement"]))
            if len(got) == 2:
                break
        assert time.monotonic() - t0 < 5.0  # nobody waited the steal clock
        assert len(got) == 2
        assert len({w for w, _ in got}) == 1  # all to the shard member
        assert all(p == "own" for _, p in got)
    finally:
        fe.stop()


def test_crashed_owner_jobs_complete_via_steal():
    """Regression (anti-starvation): a worker that stops polling must
    not strand its affinity-owned jobs past the steal timeout -- the
    live worker steals and completes them long before the dispatch
    deadline / lease expiry would fire."""
    steal_ms = 120.0
    fe = _dispatcher(affinity_steal_ms=steal_ms, lease_s=30.0)
    try:
        _attach(fe, "w-live", "w-dead")
        keys = _keys_by_owner(fe, {"w-dead"})
        jobs = [_job(key=keys["w-dead"]) for _ in range(3)]
        t0 = time.monotonic()
        for j in jobs:
            fe.queue.enqueue(TENANT, j)
        # w-dead never polls again (simulated crash); w-live keeps polling
        done = 0
        while done < len(jobs) and time.monotonic() - t0 < 5.0:
            wire = fe.poll_job(wait_s=0.3, worker_id="w-live")
            if wire is None:
                continue
            assert wire["placement"] == "steal"
            fe.complete_job(wire["id"], ok=True,
                            result={"trace": None} if wire["kind"] == "find_blocks"
                            else {"traces": [], "metrics": {}})
            done += 1
        elapsed = time.monotonic() - t0
        assert done == len(jobs)
        # stolen promptly after the timeout, nowhere near lease expiry
        assert steal_ms / 1e3 <= elapsed < 5.0
        assert all(j.done.is_set() and j.error is None for j in jobs)
    finally:
        fe.stop()


def test_poll_waiting_for_a_lost_worker_leases_nothing():
    """Regression (PR 26): a querier is killed while its long poll waits
    in the frontend; the handler outlives the socket by up to wait_s. A
    job that poll takes must go back on the queue at once, not sit in a
    lease nobody will answer for lease_s (its hedge twin could meet the
    querier's other waiting poll)."""
    import threading

    fe = _dispatcher(lease_s=30.0)
    try:
        _attach(fe, "w-live", "w-dead")
        got = []
        t = threading.Thread(target=lambda: got.append(
            fe.poll_job(wait_s=3.0, worker_id="w-dead")))
        t.start()
        time.sleep(0.2)  # the poll is waiting on the empty queue
        fe.worker_lost("w-dead")
        job = _job()
        fe.queue.enqueue(TENANT, job)
        t.join(timeout=5)
        assert not t.is_alive() and got == [None]
        assert fe._leases == {} and fe._lease_workers == {}
        wire = fe.poll_job(wait_s=1.0, worker_id="w-live")
        assert wire is not None
        fe.complete_job(wire["id"], ok=True, result={"traces": [], "metrics": {}})
        assert job.done.is_set() and job.error is None
        # the worker comes back under the same id: its new polls lease
        fe.queue.enqueue(TENANT, _job())
        assert fe.poll_job(wait_s=1.0, worker_id="w-dead") is not None
    finally:
        fe.stop()


def test_sick_owner_does_not_monopolize_retries():
    """Regression: a fast-failing but ALIVE owner polls again first and
    would win its own job back inside the steal window on every retry,
    burning MAX_RETRIES against the same corrupt state. The retry path
    demotes the job to placement-free, so a healthy peer takes it
    instantly regardless of the steal timeout."""
    fe = _dispatcher(affinity_steal_ms=60_000.0)
    try:
        _attach(fe, "w-healthy", "w-sick")
        keys = _keys_by_owner(fe, {"w-sick"})
        j = _job(key=keys["w-sick"])
        fe.queue.enqueue(TENANT, j)
        wire = fe.poll_job(wait_s=0.5, worker_id="w-sick")
        assert wire is not None and wire["placement"] == "own"
        fe.complete_job(wire["id"], ok=False, error="corrupt state",
                        retryable=True)
        wire2 = fe.poll_job(wait_s=0.5, worker_id="w-healthy")
        assert wire2 is not None and wire2["placement"] == "unowned"
        fe.complete_job(wire2["id"], ok=True,
                        result={"traces": [], "metrics": {}})
        assert j.done.is_set() and j.error is None
    finally:
        fe.stop()


def test_placement_counters_recorded():
    base = TEL.affinity_stats()["jobs"]
    fe = _dispatcher(affinity_steal_ms=10_000.0)
    try:
        _attach(fe, "w1", "w2")
        keys = _keys_by_owner(fe, {"w1"})
        fe.queue.enqueue(TENANT, _job(key=keys["w1"]))
        assert fe.poll_job(wait_s=0.5, worker_id="w1") is not None
    finally:
        fe.stop()
    now = TEL.affinity_stats()["jobs"]
    assert now.get("own", 0) >= base.get("own", 0) + 1


# ----------------------------------------------------------- per-tenant QoS


def test_query_admission_budgets():
    ov = Overrides()
    ov.defaults = replace(ov.defaults, max_concurrent_queries=2,
                          max_inflight_query_bytes=100)
    qa = QueryAdmission(ov)
    assert qa.try_admit("a", 40) is None
    assert qa.try_admit("a", 40) is None
    assert qa.try_admit("a", 1) == "concurrency"
    qa.release("a", 40)
    # byte budget: 40 in flight, +70 would breach 100
    assert qa.try_admit("a", 70) == "bytes"
    assert qa.try_admit("a", 50) is None
    # tenants are independent
    assert qa.try_admit("b", 99) is None
    qa.release("a", 40)
    qa.release("a", 50)
    qa.release("b", 99)
    assert qa.inflight("a") == (0, 0) and qa.inflight("b") == (0, 0)


def test_query_admission_first_query_always_admits():
    """A lone query larger than the tenant's own byte budget is the
    budget's unit of progress, never a livelock."""
    ov = Overrides()
    ov.defaults = replace(ov.defaults, max_inflight_query_bytes=10)
    qa = QueryAdmission(ov)
    assert qa.try_admit("a", 10_000) is None  # over budget but alone
    assert qa.try_admit("a", 1) == "bytes"
    qa.release("a", 10_000)


def test_frontend_qos_shed_429_isolated_per_tenant():
    """A tenant at its concurrency budget sheds with TooManyRequests
    (the HTTP 429) while another tenant's queries are untouched."""
    ov = Overrides()
    ov.defaults = replace(ov.defaults, max_concurrent_queries=1)
    fe = Frontend(_StubQuerier(delay=0.5), n_workers=2, overrides=ov,
                  hedge_after_s=0.0, affinity=False)
    try:
        req = SearchRequest(limit=5)
        errs: list = []

        def slow():
            try:
                fe.search("heavy", req)
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(e)

        t = threading.Thread(target=slow, daemon=True)
        t.start()
        time.sleep(0.15)  # slow() is now inside its admitted search
        with pytest.raises(TooManyRequests):
            fe.search("heavy", req)
        # an unrelated tenant is admitted while heavy is at budget
        assert fe.search("light", req) is not None
        t.join(timeout=5)
        assert not errs
        # budget returned: heavy admits again
        assert fe.search("heavy", req) is not None
    finally:
        fe.stop()


def test_qos_shed_telemetry():
    before = TEL.affinity_stats()["qos_sheds"].get("q-tel", {})
    ov = Overrides()
    ov.defaults = replace(ov.defaults, max_concurrent_queries=1)
    qa = QueryAdmission(ov)
    fe = Frontend(_StubQuerier(), n_workers=0, overrides=ov, affinity=False)
    fe.qos = qa
    try:
        assert qa.try_admit("q-tel") is None
        with pytest.raises(TooManyRequests):
            fe._qos_admit("q-tel", 0)
    finally:
        qa.release("q-tel")
        fe.stop()
    after = TEL.affinity_stats()["qos_sheds"]["q-tel"]
    assert after.get("concurrency", 0) >= before.get("concurrency", 0) + 1


def test_shed_tenant_label_escaped():
    """Tenant names come off the X-Scope-OrgID header: quotes,
    backslashes and newlines must be escaped before they reach a
    Prometheus label or one hostile client corrupts every /metrics
    scrape."""
    TEL.record_shed('ev"il\\ten\nant', "bytes")
    want = 'tenant="ev\\"il\\\\ten\\nant",budget="bytes"'
    assert TEL.qos_shed.get(labels=want) >= 1
    # the raw name is preserved in the status aggregates
    assert 'ev"il\\ten\nant' in TEL.affinity_stats()["qos_sheds"]


def test_no_overrides_means_no_gate():
    fe = _dispatcher()
    try:
        assert fe.qos is None
        assert fe._qos_admit(TENANT, 1 << 40) == 0  # never sheds
    finally:
        fe.stop()
