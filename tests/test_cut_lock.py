"""The instance lock covers a cut's SWAP, not its work (PR 37): while a
cut decodes its snapshot (`ingest:cut`) and writes the block
(`ingest:flush`) a push is acknowledged, a find returns the whole trace,
late spans land in the next block, a failure puts the snapshot back, and
the WAL directory holds every acknowledged segment."""

import ast
import inspect
import os
import random
import sys
import textwrap
import threading
import time

import pytest

from tempo_tpu.backend import MemBackend
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.db.search import SearchRequest
from tempo_tpu.db.wal import WAL
from tempo_tpu.ring.ring import InMemoryKV, Lifecycler, Ring
from tempo_tpu.services import ingester as ing_mod
from tempo_tpu.services.ingester import FLUSH_FAILURES, Ingester, IngesterConfig, Instance
from tempo_tpu.services.overrides import Overrides
from tempo_tpu.services.querier import Querier
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.testdata import make_trace, make_traces
from tempo_tpu.wire import segment

TENANT = "t-cutlock"
CUTTER = "cutter"
WAIT = 20.0  # a stuck thread fails its assert well before any suite limit
FAR = 1e9

# what makes the cut fire, with every other trigger out of reach
TRIGGERS = {
    "force": (dict(max_block_age_s=FAR, max_block_bytes=1 << 40), True),
    "age": (dict(max_block_age_s=0.0, max_block_bytes=1 << 40), False),
    "size": (dict(max_block_age_s=FAR, max_block_bytes=1), False),
}


class Gate:
    """Wraps a function of the cut's path: its first call from the
    thread named CUTTER says `entered`, then waits for `release`
    (then sleeps `sleep_s`, then raises `fail` if one is set). Calls
    from other threads (a find decodes segments too) pass through."""

    def __init__(self, fn, sleep_s: float = 0.0, fail: Exception | None = None):
        self.fn, self.sleep_s, self.fail = fn, sleep_s, fail
        self.entered, self.release = threading.Event(), threading.Event()

    def __call__(self, *a, **kw):
        if threading.current_thread().name == CUTTER and not self.entered.is_set():
            self.entered.set()
            assert self.release.wait(WAIT), "the test never released the gate"
            time.sleep(self.sleep_s)
            if self.fail is not None:
                raise self.fail
        return self.fn(*a, **kw)

    def open(self):
        self.release.set()
        return self


class Cutter:
    """`cut_block_if_ready` in a thread of its own (the `/flush`
    handler's, the sweeper's); `join()` returns what it returned or
    raised."""

    def __init__(self, inst: Instance, force: bool):
        self.out = self.err = None

        def run():
            try:
                self.out = inst.cut_block_if_ready(force=force)
            except Exception as e:  # noqa: BLE001 - handed to the test
                self.err = e

        self.t = threading.Thread(target=run, name=CUTTER, daemon=True)
        self.t.start()

    def join(self):
        self.t.join(WAIT)
        assert not self.t.is_alive(), "the cut never finished"
        return self


@pytest.fixture()
def rig(tmp_path):
    """make(trigger, wal_version) -> (db, ingester, instance, force)."""
    made = []

    def make(trigger: str = "force", wal_version: str = "w2"):
        cfg_kw, force = TRIGGERS[trigger]
        db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "dw")), backend=MemBackend())
        ing = Ingester(WAL(str(tmp_path / "w"), fsync_interval_s=0.0), db, Overrides(),
                       IngesterConfig(max_trace_idle_s=FAR, wal_version=wal_version, **cfg_kw))
        made.append(db)
        return db, ing, ing.instance(TENANT), force

    yield make
    for db in made:
        db.close()


def _gate_decode(monkeypatch, **kw) -> Gate:
    g = Gate(ing_mod.segment_to_trace, **kw)
    monkeypatch.setattr(ing_mod, "segment_to_trace", g)
    return g


def _gate_write(monkeypatch, db, **kw) -> Gate:
    g = Gate(db.write_block, **kw)
    monkeypatch.setattr(db, "write_block", g)
    return g


def _batch(traces, s=1, e=2):
    return [(tid, s, e, segment.segment_for_write(t, s, e)) for tid, t in traces]


def _late(tid: bytes, seed: int, n_spans: int = 3):
    """More spans of trace `tid` (other span ids), as one push batch."""
    return _batch([(tid, make_trace(random.Random(seed), trace_id=tid, n_spans=n_spans))])


def _push_in_thread(inst, batch) -> threading.Thread:
    t = threading.Thread(target=inst.push_segments, args=(batch,), daemon=True)
    t.start()
    t.join(WAIT)
    return t


def _stages(*names):
    s = TEL.stage_stats()
    return {n: (s.get(n, {}).get("count", 0), s.get(n, {}).get("seconds", 0.0)) for n in names}


# ------------------------------------------------- (a) a push beside a cut


@pytest.mark.parametrize("wal_version", ["w1", "w2"])
@pytest.mark.parametrize("trigger", sorted(TRIGGERS))
def test_push_is_acknowledged_while_the_cut_decodes(rig, monkeypatch, trigger, wal_version):
    db, ing, inst, force = rig(trigger, wal_version)
    traces = make_traces(6, seed=11, n_spans=4)
    inst.push_segments(_batch(traces[:4]))
    inst.cut_complete_traces(force=True)
    gate = _gate_decode(monkeypatch)
    cut = Cutter(inst, force)
    assert gate.entered.wait(WAIT), f"{trigger}: the cut never reached its decode"
    # the decode is in flight and the lock is free: the head has rotated,
    # the push lands in the new head and is acknowledged
    pusher = _push_in_thread(inst, _batch(traces[4:]))
    acked = not pusher.is_alive()
    gate.open()
    cut.join()
    assert acked, "push_segments waited for the decode"
    assert cut.err is None and cut.out is not None
    assert set(inst.live) == {tid for tid, _ in traces[4:]}
    assert not inst.cut and not inst.flushing
    for tid, t in traces[:4]:
        got = db.find_trace_by_id(TENANT, tid)
        assert got is not None and got.span_count() == t.span_count()


# ------------------------------- (b) finds beside a cut, and late spans


def test_find_sees_the_whole_trace_at_every_stage_and_late_spans_go_to_the_next_block(
        rig, monkeypatch):
    db, ing, inst, force = rig()
    kv = InMemoryKV()
    lc = Lifecycler(kv, "ing", "i0")
    lc.join()
    q = Querier(db, Ring(kv, "ing", replication_factor=1), {lc.desc.addr: ing}.__getitem__)
    traces = make_traces(5, seed=12, n_spans=4)
    tid, tr = traces[2]
    inst.push_segments(_batch(traces))
    inst.cut_complete_traces(force=True)
    decode, write = _gate_decode(monkeypatch), _gate_write(monkeypatch, db)
    cut = Cutter(inst, force)

    assert decode.entered.wait(WAIT)
    late = _late(tid, seed=99)
    n_late = segment.segment_to_trace(late[0][3]).span_count()
    whole = tr.span_count() + n_late
    assert not _push_in_thread(inst, late).is_alive()
    assert q.find_trace_by_id(TENANT, tid).span_count() == whole  # during the decode
    decode.open()
    assert write.entered.wait(WAIT)
    assert tid in inst.flushing and tid in inst.live
    assert q.find_trace_by_id(TENANT, tid).span_count() == whole  # during write_block
    write.open()
    cut.join()
    assert cut.err is None
    assert not inst.flushing and set(inst.live) == {tid}
    assert q.find_trace_by_id(TENANT, tid).span_count() == whole  # block + live head

    # the late spans are the NEXT block's, and nobody's twice
    inst.cut_complete_traces(force=True)
    assert inst.cut_block_if_ready(force=True) is not None
    per_block = [db.open_block(m).find_trace_by_id(tid) for m in db.blocklist.metas(TENANT)]
    assert sorted(t.span_count() for t in per_block if t is not None) == sorted(
        [tr.span_count(), n_late])
    assert not inst.live and not inst.cut and not inst.flushing
    assert q.find_trace_by_id(TENANT, tid).span_count() == whole


# --------------------------- (c) the readers' view of the in-flight state


def test_readers_see_the_trace_in_flushing_and_nowhere_else_meanwhile(rig, monkeypatch):
    db, ing, inst, force = rig()
    traces = make_traces(4, seed=13, n_spans=4)
    batch = _batch(traces)
    inst.push_segments(batch)
    inst.cut_complete_traces(force=True)
    decode, write = _gate_decode(monkeypatch), _gate_write(monkeypatch, db)
    cut = Cutter(inst, force)
    for gate in (decode, write):
        assert gate.entered.wait(WAIT)
        for tid, _, _, seg in batch:
            assert tid in inst.flushing and tid not in inst.cut and tid not in inst.live
            assert inst.trace_segments(tid) == [seg]
            assert inst._live_groups()[tid][:2] == [[seg], "flushing"]
        assert not db.blocklist.metas(TENANT)
        for search in (inst.search_live, inst.search_live_index):
            got = {r.trace_id for r in search(SearchRequest(limit=100)).traces}
            assert got == {tid.hex() for tid, _ in traces}
        gate.open()
    cut.join()
    assert cut.err is None and not inst.flushing
    assert inst.search_live(SearchRequest(limit=100)).traces == []


# ------------------------------------------ (d) a cut that fails midway


@pytest.mark.parametrize("failing", ["decode", "write_block"])
def test_a_failed_cut_restores_the_snapshot_before_late_spans_and_keeps_the_old_wal(
        rig, monkeypatch, failing):
    db, ing, inst, force = rig()
    traces = make_traces(3, seed=14, n_spans=4)
    tid, tr = traces[1]
    batch = _batch(traces)
    inst.push_segments(batch)
    inst.cut_complete_traces(force=True)
    old_path = inst.head.path
    boom = RuntimeError(f"{failing} failed")
    gate = (_gate_decode(monkeypatch, fail=boom) if failing == "decode"
            else _gate_write(monkeypatch, db, fail=boom))
    failures0 = FLUSH_FAILURES.get()
    cut = Cutter(inst, force)
    assert gate.entered.wait(WAIT)
    # late spans for a trace of the snapshot, cut again while it is in flight
    late = _late(tid, seed=77)
    inst.push_segments(late)
    inst.cut_complete_traces(force=True)
    assert inst.cut[tid].segments == [late[0][3]] and inst.flushing[tid].segments == [batch[1][3]]
    gate.open()
    cut.join()
    assert cut.err is boom
    assert FLUSH_FAILURES.get() == failures0 + 1
    assert not inst.flushing and not inst.live
    assert set(inst.cut) == {t for t, _ in traces}
    assert inst.cut[tid].segments == [batch[1][3], late[0][3]]  # snapshot first
    assert inst.cut[tid].nbytes == len(batch[1][3]) + len(late[0][3])
    assert os.path.exists(old_path) and inst.head.path != old_path
    assert not db.blocklist.metas(TENANT)
    # the retry cuts everything the failed one held
    assert inst.cut_block_if_ready(force=True) is not None
    got = db.find_trace_by_id(TENANT, tid)
    assert got.span_count() == tr.span_count() + segment.segment_to_trace(
        late[0][3]).span_count()
    assert not inst.cut and not inst.flushing


# ----------------------------------------------- (e) crash consistency


@pytest.mark.parametrize("wal_version", ["w1", "w2"])
@pytest.mark.parametrize("trigger", ["force", "age"])
def test_between_swap_and_landing_the_wal_holds_every_acknowledged_segment(
        rig, monkeypatch, trigger, wal_version):
    """A SIGKILL while the cut decodes: what a restart would replay. A
    forced cut carries nothing, so every segment is in exactly one file;
    a cut by age leaves live traces behind, and those are BY DESIGN in
    both (fsynced into the new head under the lock, so that the old file
    can go once the block lands; replay combines them by span id)."""
    db, ing, inst, force = rig(trigger, wal_version)
    traces = make_traces(9, seed=15, n_spans=4)
    cut_b, live_b, after_b = _batch(traces[:4]), _batch(traces[4:7]), _batch(traces[7:])
    inst.push_segments(cut_b)
    inst.cut_complete_traces(force=True)
    inst.push_segments(live_b)
    if force:  # the /flush handler's order: everything live is cut first
        inst.cut_complete_traces(force=True)
        cut_b, live_b = cut_b + live_b, []
    old_path = inst.head.path
    gate = _gate_decode(monkeypatch)
    cut = Cutter(inst, force)
    assert gate.entered.wait(WAIT)
    assert not _push_in_thread(inst, after_b).is_alive()  # acknowledged mid-cut
    files = {rb.path: [r.segment for r in rb.records]
             for rb in WAL(inst.wal.dir).rescan_blocks()}
    gate.open()
    cut.join()
    assert cut.err is None
    new_path = inst.head.path
    assert set(files) == {old_path, new_path}

    def segs(batch):
        return sorted(seg for *_, seg in batch)

    assert sorted(files[old_path]) == segs(cut_b + live_b)
    assert sorted(files[new_path]) == segs(live_b + after_b)
    everywhere = files[old_path] + files[new_path]
    for *_, seg in cut_b + after_b:
        assert everywhere.count(seg) == 1
    for *_, seg in live_b:
        assert everywhere.count(seg) == 2
    # landed: the old file is gone, the new head alone holds what is live
    left = {rb.path: sorted(r.segment for r in rb.records)
            for rb in WAL(inst.wal.dir).rescan_blocks()}
    assert left == {new_path: segs(live_b + after_b)}


# ------------------------------------- (f) the stage table and the source


def test_one_swap_one_cut_one_flush_a_cut_and_the_swap_is_short(rig, monkeypatch):
    db, ing, inst, force = rig()
    names = ("ingest:swap", "ingest:cut", "ingest:flush")
    before = _stages(*names)
    assert inst.cut_block_if_ready(force=True) is None  # nothing cut: no stage at all
    assert _stages(*names) == before
    inst.push_segments(_batch(make_traces(5, seed=16, n_spans=4)))
    inst.cut_complete_traces(force=True)
    gate = _gate_decode(monkeypatch, sleep_s=0.5).open()
    assert Cutter(inst, force).join().err is None
    assert gate.entered.is_set()
    after = _stages(*names)
    for n in names:
        assert after[n][0] - before[n][0] == 1, n
    assert after["ingest:cut"][1] - before["ingest:cut"][1] >= 0.5
    assert after["ingest:swap"][1] - before["ingest:swap"][1] < 0.25
    assert "swap" in TEL.ingest_stats()["stages"]


def test_nothing_under_the_lock_of_a_cut_decodes_or_writes():
    """Source-level: no `with self.lock` block of `cut_block_if_ready`
    calls the decode, the combine or the block write."""
    fn = ast.parse(textwrap.dedent(inspect.getsource(Instance.cut_block_if_ready))).body[0]
    locked = [w for w in ast.walk(fn) if isinstance(w, ast.With)
              and any(ast.unparse(i.context_expr) == "self.lock" for i in w.items)]
    assert len(locked) == 3  # the swap, the failure path, the retirement
    banned = {"segment_to_trace", "combine_traces", "sort_trace", "write_block"}
    for w in locked:
        called = {getattr(c.func, "attr", getattr(c.func, "id", None))
                  for c in ast.walk(w) if isinstance(c, ast.Call)}
        assert not called & banned, called & banned
    everywhere = {getattr(c.func, "attr", getattr(c.func, "id", None))
                  for c in ast.walk(fn) if isinstance(c, ast.Call)}
    assert banned <= everywhere  # the work is still this function's


# ------------------------------------------- pushes, finds and cuts at once


def test_every_acknowledged_span_lands_exactly_once_under_concurrent_cuts(rig):
    """More threads than cores, a short switch interval: pushers send
    several windows a trace while one thread cuts and another finds.
    Every acknowledged span ends in exactly one block, a find never
    sees a trace shrink, and nothing stays behind in a lifecycle dict."""
    db, ing, inst, _ = rig()
    n_pushers, per_pusher, windows = 8, 6, 3
    plans = []
    for p in range(n_pushers):
        traces = make_traces(per_pusher, seed=1000 + p, n_spans=2)
        plans.append([[_late(tid, seed=p * 100 + w * 10 + i, n_spans=2)[0]
                       for i, (tid, _) in enumerate(traces)] for w in range(windows)])
    want = {}
    for plan in plans:
        for window in plan:
            for tid, _, _, seg in window:
                want[tid] = want.get(tid, 0) + segment.segment_to_trace(seg).span_count()
    errors, stop = [], threading.Event()
    seen: dict[bytes, int] = {}

    def guard(fn, *args):
        def run():
            try:
                fn(*args)
            except Exception as e:  # noqa: BLE001 - reported by the test
                errors.append(e)
        return run

    def pusher(plan):
        for window in plan:
            inst.push_segments(window)
            time.sleep(0.002)

    def cutter():
        while not stop.is_set():
            inst.cut_complete_traces(force=True)
            inst.cut_block_if_ready(force=True)

    def finder():
        tids = sorted(want)
        while not stop.is_set():
            for tid in tids:
                live = inst.find_trace_by_id(tid)
                blocks = db.find_trace_by_id(TENANT, tid)
                n = sum(t.span_count() for t in (live, blocks) if t is not None)
                # the block is listed before the live copy retires, so a
                # find may count a span in both legs, never in neither
                assert n >= seen.get(tid, 0), (tid.hex(), n, seen.get(tid))
                seen[tid] = min(n, want[tid])

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        side = [threading.Thread(target=guard(f), daemon=True) for f in (cutter, finder)]
        pushers = [threading.Thread(target=guard(pusher, plan), daemon=True) for plan in plans]
        for t in side + pushers:
            t.start()
        for t in pushers:
            t.join(WAIT)
        stop.set()
        for t in side:
            t.join(WAIT)
        assert not any(t.is_alive() for t in side + pushers)
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)
    assert not inst.live and not inst.cut and not inst.flushing
    got = {tid: 0 for tid in want}
    for m in db.blocklist.metas(TENANT):
        blk = db.open_block(m)
        for tid in want:
            t = blk.find_trace_by_id(tid)
            got[tid] += t.span_count() if t is not None else 0
    assert got == want
