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


# ------------------------------------------- warm steals (held replicas)


def _warm_steals() -> int:
    return TEL.affinity_stats()["warm_steals"]


def _poll(fe: Frontend, worker: str, holds=None, wait_s: float = 0.5):
    return fe.poll_job(wait_s=wait_s, worker_id=worker, staged_blocks=holds)


@pytest.mark.parametrize("as_type", [list, tuple, frozenset])
def test_holder_steals_at_once(as_type):
    """A non-owner whose newest poll reported the job's block among the
    blocks it holds claims the job without the steal clock: stamped
    `steal` (a non-owner ran it), marked warm, counted once."""
    fe = _dispatcher(affinity_steal_ms=60_000.0)
    try:
        _attach(fe, "w1", "w2")
        key = _keys_by_owner(fe, {"w1"})["w1"]
        job = _job(key=key)
        fe.queue.enqueue(TENANT, job)
        w0, s0 = _warm_steals(), TEL.affinity_stats()["jobs"].get("steal", 0)
        t0 = time.monotonic()
        wire = _poll(fe, "w2", as_type([key, "another-block"]))
        assert wire is not None and time.monotonic() - t0 < 5.0
        assert wire["placement"] == "steal"  # nothing new on the wire
        assert set(wire) == {"id", "tenant", "kind", "payload", "placement",
                             "deadline_in_s", "trace"}
        assert job.placement == "steal" and job.warm is True
        assert _warm_steals() == w0 + 1
        assert TEL.affinity_stats()["jobs"]["steal"] == s0 + 1
    finally:
        fe.stop()


@pytest.mark.parametrize("holds", [None, [], ["some-other-block"], "not-a-list"],
                         ids=["no-field", "empty", "other-block", "malformed"])
def test_cold_thief_waits_the_clock(holds):
    """A domain that does not hold the block -- or an older querier
    whose poll carries no set at all -- waits AFFINITY_STEAL_MS as
    before, and its steal is not a warm one."""
    steal_ms = 120.0
    fe = _dispatcher(affinity_steal_ms=steal_ms)
    try:
        _attach(fe, "w1", "w2")
        key = _keys_by_owner(fe, {"w1"})["w1"]
        job = _job(key=key)
        w0 = _warm_steals()
        fe.queue.enqueue(TENANT, job)
        t0 = time.monotonic()
        assert _poll(fe, "w2", holds, wait_s=0.03) is None  # clock running
        assert job.placement == ""
        wire = _poll(fe, "w2", holds, wait_s=2.0)
        waited = time.monotonic() - t0
        assert wire is not None and wire["placement"] == "steal"
        assert steal_ms / 1e3 <= waited < 2.0
        assert job.warm is False and _warm_steals() == w0
    finally:
        fe.stop()


def test_owner_and_unowned_do_not_need_the_set():
    """The owner still claims as `own` and a block-free job as
    `unowned`, whatever either side reported: neither is a warm steal."""
    fe = _dispatcher(affinity_steal_ms=60_000.0)
    try:
        _attach(fe, "w1", "w2")
        key = _keys_by_owner(fe, {"w1"})["w1"]
        w0 = _warm_steals()
        mine, free = _job(key=key), _job()
        fe.queue.enqueue(TENANT, mine)
        assert _poll(fe, "w1", [key])["placement"] == "own"
        fe.queue.enqueue(TENANT, free)
        assert _poll(fe, "w2", [key])["placement"] == "unowned"
        assert not mine.warm and not free.warm and _warm_steals() == w0
    finally:
        fe.stop()


def test_reported_set_is_replaced_by_the_next_poll():
    """The frontend keeps the NEWEST set a worker id reported: a block
    evicted since the last poll is no longer a reason to skip the clock."""
    fe = _dispatcher(affinity_steal_ms=60_000.0)
    try:
        _attach(fe, "w1", "w2")
        key = _keys_by_owner(fe, {"w1"})["w1"]
        assert _poll(fe, "w2", [key], wait_s=0.01) is None
        assert fe._remote_holds["w2"] == frozenset([key])
        assert _poll(fe, "w2", ["elsewhere"], wait_s=0.01) is None
        assert fe._remote_holds["w2"] == frozenset(["elsewhere"])
        fe.queue.enqueue(TENANT, _job(key=key))
        assert _poll(fe, "w2", ["elsewhere"], wait_s=0.05) is None
        assert _poll(fe, "w2", wait_s=0.05) is None  # no field: holds nothing
        assert fe._remote_holds["w2"] == frozenset()
        wire = _poll(fe, "w2", [key])
        assert wire is not None and wire["placement"] == "steal"
    finally:
        fe.stop()


@pytest.mark.parametrize("how", ["worker_lost", "worker_expiry_s"])
def test_reported_set_is_forgotten_with_the_worker(how):
    fe = _dispatcher(worker_expiry_s=0.05 if how == "worker_expiry_s" else 60.0)
    try:
        assert _poll(fe, "w1", ["a"], wait_s=0.01) is None
        assert _poll(fe, "w2", ["b"], wait_s=0.01) is None
        assert set(fe._remote_holds) == {"w1", "w2"}
        if how == "worker_lost":
            fe.worker_lost("w2")
        else:
            time.sleep(0.08)
            assert _poll(fe, "w1", ["a"], wait_s=0.01) is None  # w1 lives on
            fe._affinity_members()
        assert set(fe._remote_holds) == {"w1"}
        assert {d.instance_id for d in fe._affinity_members()} == {"w1"}
    finally:
        fe.stop()


def test_warm_steals_count_exactly_the_early_steals():
    """`affinity.warm_steals` counts the steals taken without the clock
    and nothing else: not the owner's jobs, not the cold steals, and a
    holder that arrives after the clock ran out steals like anybody."""
    steal_ms = 80.0
    fe = _dispatcher(affinity_steal_ms=steal_ms)
    try:
        _attach(fe, "w1", "w2")
        key = _keys_by_owner(fe, {"w1"})["w1"]
        base = TEL.affinity_stats()
        jobs = [_job(key=key) for _ in range(5)]
        for j in jobs[:3]:
            fe.queue.enqueue(TENANT, j)
        assert _poll(fe, "w1", [key])["placement"] == "own"
        assert _poll(fe, "w2", [key])["placement"] == "steal"  # early
        wire = _poll(fe, "w2", [], wait_s=2.0)  # cold: the clock
        assert wire["placement"] == "steal"
        fe.queue.enqueue(TENANT, jobs[3])
        time.sleep(steal_ms / 1e3 + 0.02)
        assert _poll(fe, "w2", [key])["placement"] == "steal"  # late holder
        fe.queue.enqueue(TENANT, jobs[4])
        assert _poll(fe, "w2", [key])["placement"] == "steal"  # early
        now = TEL.affinity_stats()
        assert now["warm_steals"] - base["warm_steals"] == 2
        assert now["jobs"]["steal"] - base["jobs"].get("steal", 0) == 4
        assert now["jobs"]["own"] - base["jobs"].get("own", 0) == 1
        assert [j.warm for j in jobs] == [False, True, False, False, True]
    finally:
        fe.stop()


def test_requeued_job_sheds_its_warm_mark():
    """A re-dispatched job carries neither its old placement nor its
    old warm mark into the next dequeue."""
    q = RequestQueue()
    j = _job(key="b")
    j.placement, j.warm = "steal", True
    q.enqueue(TENANT, j)
    assert j.placement == "" and j.warm is False


def test_batch_extras_ride_a_warm_claim():
    """Same-key window mates join the lead's claim as before: one warm
    steal takes them all, each counted as a steal and a warm one."""
    fe = _dispatcher(affinity_steal_ms=60_000.0)
    try:
        _attach(fe, "w1", "w2")
        key = _keys_by_owner(fe, {"w1"})["w1"]
        bk = ("search_blocks", TENANT, (key,))
        mates = [_job(key=key, batch_key=bk) for _ in range(3)]
        for j in mates:
            fe.queue.enqueue(TENANT, j)
        base = TEL.affinity_stats()
        wire = _poll(fe, "w2", [key])
        assert wire["kind"] == "multi" and wire["placement"] == "steal"
        assert len(wire["payload"]["jobs"]) == 3
        assert all(j.placement == "steal" and j.warm for j in mates)
        now = TEL.affinity_stats()
        assert now["warm_steals"] - base["warm_steals"] == 3
        assert now["jobs"]["steal"] - base["jobs"].get("steal", 0) == 3
    finally:
        fe.stop()


def test_local_pool_reads_its_own_process_set(monkeypatch):
    """The local member asks ops/stage what THIS process holds: with the
    block resident its workers take a remote owner's job at once,
    without it they leave the job to the clock."""
    from tempo_tpu.ops import stage

    held: set = set()
    monkeypatch.setattr(stage, "staged_block_ids", lambda: frozenset(held))
    fe = Frontend(_StubQuerier(), n_workers=1, affinity=True,
                  affinity_steal_ms=60_000.0)
    try:
        _attach(fe, "w1")
        keys = [f"blk-{i:03d}" for i in range(64)]
        remote = [k for k in keys if _owner_of(fe, k) == "w1"]
        assert len(remote) >= 2
        # the worker's first pass began while it was the only domain
        # (the legacy dequeue): end it, the next one builds a claimer
        first = threading.Event()
        fe.queue.enqueue(TENANT, _job(fn=first.set))
        assert first.wait(5.0)
        ran = threading.Event()
        cold = _job(key=remote[0], fn=ran.set)
        fe.queue.enqueue(TENANT, cold)
        assert not ran.wait(0.3) and cold.placement == ""
        wire = _poll(fe, "w1")  # the owner takes it
        assert wire["placement"] == "own"
        held.add(remote[1])
        w0 = _warm_steals()
        warm = _job(key=remote[1], fn=ran.set)
        # (the worker's current pass still holds the empty set: at most
        # one dequeue timeout, 1 s, until it reads the new one)
        fe.queue.enqueue(TENANT, warm)
        assert ran.wait(5.0)
        assert warm.placement == "steal" and warm.warm is True
        assert _warm_steals() == w0 + 1
    finally:
        fe.stop()


@pytest.mark.parametrize("case", ["single-domain", "affinity-off", "env-off"])
def test_reported_sets_leave_the_legacy_dequeue_alone(case, monkeypatch):
    """One cache domain or TEMPO_AFFINITY=0: no claimer is built, the
    dequeue is the head-of-queue path and nothing is stamped or counted,
    whatever the polls report."""
    if case == "env-off":
        monkeypatch.setenv("TEMPO_AFFINITY", "0")
    fe = _dispatcher(affinity=False if case == "affinity-off" else
                     None if case == "env-off" else True)
    try:
        workers = ["only"] if case == "single-domain" else ["w1", "w2"]
        for w in workers:
            assert _poll(fe, w, ["blk"], wait_s=0.01) is None
        assert all(fe._claimer(w) is None for w in workers)
        base = TEL.affinity_stats()
        jobs = [_job(key=k) for k in ("blk", "other", "blk")]
        for j in jobs:
            fe.queue.enqueue(TENANT, j)
        for j in jobs:  # strict FIFO, no placement
            wire = _poll(fe, workers[-1], ["blk"])
            assert wire is not None and wire["placement"] == ""
            assert j.handed_wall and not j.warm and j.placement == ""
        now = TEL.affinity_stats()
        assert now["jobs"] == base["jobs"]
        assert now["warm_steals"] == base["warm_steals"]
    finally:
        fe.stop()


def test_warm_steals_sum_over_a_tree():
    """A tree's /status/kernels adds `affinity.warm_steals` over its
    instances like the rest of the section."""
    from tempo_tpu.services.proctree import tree_kernel_status

    def inst(k: int) -> dict:
        return {"device": {"count": 1},
                "affinity": {"jobs": {"own": 4 * k, "steal": 2 * k, "unowned": k},
                             "warm_steals": k,
                             "staged_by_placement": {
                                 "steal": {"hits": 9 * k, "misses": k,
                                           "hit_rate": 0.9}},
                             "qos_sheds": {}}}

    total = tree_kernel_status(inst(1), [({"index": i}, inst(i + 1))
                                         for i in (1, 2, 3)])
    assert total["affinity"]["warm_steals"] == 10
    assert total["affinity"]["jobs"] == {"own": 40, "steal": 20, "unowned": 10}
    assert total["affinity"]["staged_by_placement"]["steal"] == {
        "hits": 90, "misses": 10, "hit_rate": 0.9}


def test_dispatch_span_says_warm():
    """`job:dispatch` keeps placement = stolen and says whether the
    steal was a warm one; owned and unowned jobs carry no such key."""
    spans = []

    class _Trace:
        root_id = b"r"

        def child(self, name, t0, t1, attrs, parent=None, span_id=None):
            spans.append((name, dict(attrs)))
            return b"s"

    fe = _dispatcher()
    try:
        jobs = []
        for placement, warm in (("steal", True), ("steal", False),
                                ("own", False), ("unowned", False)):
            j = _job(key="b")
            j.placement, j.warm, j.worker = placement, warm, "w2"
            j.started_wall, j.handed_wall, j.done_at = 1.0, 1.5, 2.0
            jobs.append(j)
        fe._emit_self_trace(jobs, _Trace())
    finally:
        fe.stop()
    disp = [a for n, a in spans if n == "job:dispatch"]
    assert [(a["placement"], a.get("warm")) for a in disp] == [
        ("stolen", True), ("stolen", False), ("owner", None), ("unowned", None)]


def test_querier_polls_say_what_it_holds(monkeypatch):
    """A querier reports ops/stage's block ids with every poll, next to
    `device`, and the frontend's poll route passes them on."""
    from tempo_tpu.ops import stage
    from tempo_tpu.services import worker as W
    from tempo_tpu.transport import client as TC

    monkeypatch.setattr(stage, "staged_block_ids",
                        lambda: frozenset({"blk-b", "blk-a"}))
    w = W.QuerierWorker(_StubQuerier(), ["http://frontend.invalid"],
                        concurrency=1, worker_id="querier-1",
                        device={"platform": "cpu"})
    polls = []

    def post(addr, path, payload, timeout):
        polls.append((path, payload))
        w.stop()

    w._post = post
    w._loop("http://frontend.invalid")
    assert polls == [("/internal/jobs/poll", {
        "wait_s": w.poll_wait_s, "worker_id": "querier-1",
        "device": {"platform": "cpu"}, "staged_blocks": ["blk-a", "blk-b"]})]

    fe = _dispatcher()
    try:
        app = SimpleNamespace(frontend=fe, cfg=SimpleNamespace(target="all"))
        payload = dict(polls[0][1], wait_s=0.01)
        assert TC.handle_internal(app, "/internal/jobs/poll", payload) == (200, {})
        assert fe._remote_holds["querier-1"] == frozenset({"blk-a", "blk-b"})
        assert fe.attached_workers() == {"querier-1": {"platform": "cpu"}}
    finally:
        fe.stop()


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
