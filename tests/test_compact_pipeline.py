"""Pipelined concurrent compaction (db/compact_pipeline).

The load-bearing guarantees, each with its own test:
  * differential: pipelined output blocks are BIT-identical to a
    sequential compact() run -- multi-output jobs, with trace-id
    collisions across inputs;
  * crash/ordering: a failure injected between output writes leaves no
    input mark_compacted, nothing visible to blocklist polling, and a
    re-run converges;
  * compression matrix: the pipeline runs on the zlib zstd-shim
    (images without the zstandard wheel) and with the native
    gather_runs/dict_union helpers unavailable;
  * scheduling: per-tenant round-robin admission, the host-RAM
    admission gate never deadlocks, and the service-level sweep
    (TEMPO_COMPACT_CONCURRENCY) updates the blocklist per job.
Plus the select_jobs regression: an input block larger than
max_block_bytes must cut the batch on its own, never batch with more.
"""

from __future__ import annotations

import shutil
import threading

import pytest

from tempo_tpu.backend import MemBackend
from tempo_tpu.backend.base import DoesNotExist
from tempo_tpu.backend.local import LocalBackend
from tempo_tpu.block.builder import BLOOM_PREFIX, build_block_from_traces
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.db import compactor as comp
from tempo_tpu.db.blocklist import Poller
from tempo_tpu.db.compact_pipeline import CompactionPipeline
from tempo_tpu.db.compactor import CompactionJob, CompactorConfig, compact
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.testdata import make_traces

TENANT = "t1"


def _meta(size: int, level: int = 0, end_ns: int = 1_700_000_000 * 10**9):
    from tempo_tpu.block.meta import BlockMeta

    m = BlockMeta.new(TENANT)
    m.size_bytes = size
    m.compaction_level = level
    m.end_time_unix_nano = end_ns
    return m


# ------------------------------------------------------ select_jobs fix
def test_select_jobs_oversized_block_cuts_batch():
    """Regression: a single input block larger than max_block_bytes used
    to be admitted (the size guard only fired once the batch was
    non-empty) and then batched with further blocks."""
    cfg = CompactorConfig(max_block_bytes=100, min_input_blocks=2,
                          max_input_blocks=10, active_window_s=10**12)
    big = _meta(500)
    smalls = [_meta(10) for _ in range(3)]
    jobs = comp.select_jobs(TENANT, [big] + smalls, cfg)
    assert jobs, "small blocks must still batch"
    picked = {m.block_id for j in jobs for m in j.blocks}
    assert big.block_id not in picked
    assert picked == {m.block_id for m in smalls}
    # all-oversized group: no job at all (merging any two would exceed)
    jobs2 = comp.select_jobs(TENANT, [_meta(500), _meta(600)], cfg)
    assert jobs2 == []


# ------------------------------------------------------------- helpers
def _build_inputs(backend, n_blocks: int = 4, n_traces: int = 30,
                  collide: bool = True) -> list:
    """n_blocks small blocks; with collide=True consecutive blocks share
    some trace ids (replicated partial traces -- the collision path)."""
    metas = []
    for b in range(n_blocks):
        traces = make_traces(n_traces, seed=100 + b, n_spans=4)
        if collide and b:
            prev = make_traces(n_traces, seed=100 + b - 1, n_spans=4)
            traces = sorted(traces[:-3] + prev[:3], key=lambda p: p[0])
        metas.append(build_block_from_traces(backend, TENANT, traces))
    return metas


def _output_objects(backend, meta) -> dict[str, bytes]:
    out = {}
    for name in ("data.vtpu", "dict.vtpu"):
        out[name] = backend.read(TENANT, meta.block_id, name)
    for s in range(meta.bloom_shards):
        out[f"{BLOOM_PREFIX}{s}"] = backend.read(
            TENANT, meta.block_id, f"{BLOOM_PREFIX}{s}")
    return out


# ---------------------------------------------------------- differential
def test_pipeline_bit_identical_to_sequential(tmp_path):
    """Multi-output jobs with cross-block id collisions: every output
    object (data, dictionary, bloom shards) byte-equal between the
    sequential driver and the pipelined executor."""
    a = LocalBackend(str(tmp_path / "a"))
    metas = _build_inputs(a, n_blocks=4)
    shutil.copytree(str(tmp_path / "a"), str(tmp_path / "b"))
    b = LocalBackend(str(tmp_path / "b"))

    # tiny target -> several output blocks per job; concat disabled so
    # the columnar merge (the pipelined stage split) is what runs
    cfg = CompactorConfig(concat_small_input_bytes=0, target_block_bytes=16000)
    jobs_a = [CompactionJob(TENANT, metas[:2]), CompactionJob(TENANT, metas[2:])]
    seq = [compact(a, j, cfg) for j in jobs_a]
    assert any(len(r.new_blocks) > 1 for r in seq), "want a multi-output job"

    jobs_b = [CompactionJob(TENANT, metas[:2]), CompactionJob(TENANT, metas[2:])]
    outs = CompactionPipeline(b, cfg, concurrency=4).run({TENANT: jobs_b})
    assert [o.error for o in outs] == [None, None]

    for rs, oc in zip(seq, outs):
        rp = oc.result
        assert rp.traces_out == rs.traces_out and rp.spans_out == rs.spans_out
        assert len(rp.new_blocks) == len(rs.new_blocks)
        for ms, mp in zip(rs.new_blocks, rp.new_blocks):
            assert _output_objects(a, ms) == _output_objects(b, mp)


# -------------------------------------------------------- crash/ordering
def test_pipeline_crash_between_outputs_is_invisible(tmp_path, monkeypatch):
    """Fail the SECOND output write of a multi-output job: no input may
    be mark_compacted, no partial output may surface to blocklist
    polling, and an unpatched re-run converges."""
    import tempo_tpu.db.columnar_compact as cc

    backend = MemBackend()
    metas = _build_inputs(backend, n_blocks=3, collide=False)
    cfg = CompactorConfig(concat_small_input_bytes=0, target_block_bytes=16000,
                          prefetch_depth=0)
    job = CompactionJob(TENANT, list(metas))

    real_write = cc.write_block
    calls = {"n": 0}

    def boom(*args, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise OSError("injected: disk died between outputs")
        return real_write(*args, **kw)

    monkeypatch.setattr(cc, "write_block", boom)
    outs = CompactionPipeline(backend, cfg, concurrency=2).run(
        {TENANT: [job]})
    assert len(outs) == 1 and isinstance(outs[0].error, OSError)
    assert calls["n"] >= 2, "the job must have attempted multiple outputs"

    # no input consumed, nothing new visible
    for m in metas:
        assert not backend.has_object(TENANT, m.block_id, "meta.compacted.json")
    polled, compacted = Poller(backend, build_index=False).poll()
    assert {m.block_id for m in polled[TENANT]} == {m.block_id for m in metas}
    assert not compacted.get(TENANT)

    # re-run (no fault) converges
    monkeypatch.setattr(cc, "write_block", real_write)
    outs2 = CompactionPipeline(backend, cfg, concurrency=2).run(
        {TENANT: [CompactionJob(TENANT, list(metas))]})
    assert outs2[0].error is None
    res = outs2[0].result
    assert len(res.new_blocks) >= 2
    polled2, _ = Poller(backend, build_index=False).poll()
    live = {m.block_id for m in polled2[TENANT] if not m.compacted_at_unix}
    assert {m.block_id for m in res.new_blocks} <= live
    for m in metas:
        assert backend.has_object(TENANT, m.block_id, "meta.compacted.json")


# ---------------------------------------------------- compression matrix
def test_pipeline_on_zstd_shim_and_without_native(tmp_path, monkeypatch):
    """CI images carry no zstandard wheel and may lack the native
    helpers: pin the zlib shim codec AND the pure-Python fallbacks
    (gather_runs -> numpy indexing, dict_union -> numpy merge, fused
    remap off) and prove the pipeline still matches sequential output
    byte-for-byte."""
    import tempo_tpu.block.colio as colio
    import tempo_tpu.block.dictionary as dictionary
    import tempo_tpu.native as native
    from tempo_tpu.util import zstdshim

    monkeypatch.setattr(colio, "zstandard", zstdshim)
    # this thread may hold a real-zstd decompressor cached by an earlier
    # test: it must not read the shim's frames
    monkeypatch.setattr(colio, "_DCTX_LOCAL", threading.local())
    monkeypatch.setattr(dictionary, "zstandard", zstdshim)
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", True)
    assert not native.available()

    a = LocalBackend(str(tmp_path / "a"))
    metas = _build_inputs(a, n_blocks=4)
    shutil.copytree(str(tmp_path / "a"), str(tmp_path / "b"))
    b = LocalBackend(str(tmp_path / "b"))

    cfg = CompactorConfig(concat_small_input_bytes=0, target_block_bytes=16000)
    jobs = lambda ms: [CompactionJob(TENANT, ms[:2]), CompactionJob(TENANT, ms[2:])]  # noqa: E731
    seq = [compact(a, j, cfg) for j in jobs(metas)]
    outs = CompactionPipeline(b, cfg, concurrency=3).run({TENANT: jobs(metas)})
    assert [o.error for o in outs] == [None, None]
    for rs, oc in zip(seq, outs):
        for ms, mp in zip(rs.new_blocks, oc.result.new_blocks):
            assert _output_objects(a, ms) == _output_objects(b, mp)
    # the outputs are readable (shim round-trip, not just equal garbage)
    from tempo_tpu.block.versioned import open_block_versioned

    blk = open_block_versioned(b, outs[0].result.new_blocks[0])
    assert blk.materialize_traces([0])[0].span_count() > 0


def test_pipeline_falls_back_when_assemble_refuses_late(tmp_path, monkeypatch):
    """UnsupportedColumnar can surface AFTER planning (e.g. an unknown
    column family in _assemble): the pipeline must fall back to the
    wire merge like the sequential driver, not strand the job as a
    permanent error."""
    import tempo_tpu.db.columnar_compact as cc

    backend = MemBackend()
    metas = _build_inputs(backend, n_blocks=2, collide=False)
    cfg = CompactorConfig(concat_small_input_bytes=0, prefetch_depth=0)

    def refuse(plan, cfg_):
        raise cc.UnsupportedColumnar("late refusal (fixture)")
        yield  # noqa: unreachable -- keeps this a generator like the real one

    monkeypatch.setattr(cc, "iter_outputs", refuse)
    outs = CompactionPipeline(backend, cfg, concurrency=2).run(
        {TENANT: [CompactionJob(TENANT, list(metas))]})
    assert outs[0].error is None, outs[0].error
    res = outs[0].result
    assert res.new_blocks and res.traces_out > 0
    for m in metas:
        assert backend.has_object(TENANT, m.block_id, "meta.compacted.json")


def test_pipeline_falls_back_when_plan_refuses(tmp_path, monkeypatch):
    """Plan-stage refusal (e.g. differing column sets) must route the
    already-fetched job straight to the wire merge -- once, not via a
    second full fetch+decode through compact()."""
    import tempo_tpu.db.columnar_compact as cc

    backend = MemBackend()
    metas = _build_inputs(backend, n_blocks=2, collide=False)
    cfg = CompactorConfig(concat_small_input_bytes=0, prefetch_depth=0)

    real_plan = cc.plan_columnar
    plan_calls = {"n": 0}

    def refuse(*a, **kw):
        plan_calls["n"] += 1
        raise cc.UnsupportedColumnar("differing column sets (fixture)")

    monkeypatch.setattr(cc, "plan_columnar", refuse)
    outs = CompactionPipeline(backend, cfg, concurrency=2).run(
        {TENANT: [CompactionJob(TENANT, list(metas))]})
    monkeypatch.setattr(cc, "plan_columnar", real_plan)
    assert outs[0].error is None, outs[0].error
    assert plan_calls["n"] == 1, "fallback must not re-plan through compact()"
    res = outs[0].result
    assert res.new_blocks and res.traces_out > 0
    for m in metas:
        assert backend.has_object(TENANT, m.block_id, "meta.compacted.json")


def test_select_jobs_oversized_does_not_cut_neighbors():
    """Skipping an oversized block must not flush the batch in progress:
    its smaller neighbors still compact together."""
    cfg = CompactorConfig(max_block_bytes=100, min_input_blocks=2,
                          max_input_blocks=10, active_window_s=10**12)
    metas = [_meta(10), _meta(500), _meta(20)]
    jobs = comp.select_jobs(TENANT, metas, cfg)
    assert len(jobs) == 1
    assert {m.block_id for m in jobs[0].blocks} == {
        metas[0].block_id, metas[2].block_id}


# ----------------------------------------------------------- scheduling
def test_round_robin_interleaves_tenants():
    pipe = CompactionPipeline(MemBackend(), CompactorConfig())
    j = lambda t, i: CompactionJob(t, [_meta(10)], hash=f"{t}-{i}")  # noqa: E731
    tickets = pipe._round_robin({
        "a": [j("a", 0), j("a", 1), j("a", 2)],
        "b": [j("b", 0)],
        "c": [j("c", 0), j("c", 1)],
    })
    assert [t.tenant for t in tickets] == ["a", "b", "c", "a", "c", "a"]


def test_admission_gate_tiny_budget_never_deadlocks(tmp_path):
    """A budget smaller than any single job must still admit one at a
    time (serial) and finish every job."""
    backend = LocalBackend(str(tmp_path / "s"))
    metas = _build_inputs(backend, n_blocks=4, collide=False)
    cfg = CompactorConfig(concat_small_input_bytes=0,
                          pipeline_mem_budget_bytes=1)
    jobs = [CompactionJob(TENANT, metas[:2]), CompactionJob(TENANT, metas[2:])]
    outs = CompactionPipeline(backend, cfg, concurrency=4).run({TENANT: jobs})
    assert [o.error for o in outs] == [None, None]


def test_compact_tenants_updates_blocklist_and_telemetry(tmp_path):
    """The TempoDB-level concurrent sweep: per-job blocklist updates land
    (inputs gone from live, outputs present), and the kerneltel
    compaction section advances."""
    mark = TEL.compaction_stats()
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "wal")),
                 backend=MemBackend())
    db.cfg.compaction.concurrency = 3
    db.cfg.compaction.concat_small_input_bytes = 0
    db.cfg.compaction.min_input_blocks = 2
    for t in ("ta", "tb"):
        for b in range(2):
            db.blocklist.update(t, add=[build_block_from_traces(
                db.backend, t, make_traces(20, seed=7 * b + (t == "tb"),
                                           n_spans=3))])
    outcomes = db.compact_tenants()
    assert [oc.error for oc in outcomes] == [None, None]
    assert {oc.tenant for oc in outcomes} == {"ta", "tb"}
    for t in ("ta", "tb"):
        live = db.blocklist.metas(t)
        assert all(m.compaction_level >= 1 for m in live)
        assert db.blocklist.compacted_metas(t)
    now = TEL.compaction_stats()
    assert now["jobs"] - mark["jobs"] == 2
    assert now["runs"] - mark["runs"] == 1
    assert now["stage_seconds"], "per-stage histogram section populated"
    db.close()


def test_service_sweep_uses_pipeline(tmp_path, monkeypatch):
    """services/compactor routes through the pipeline when
    TEMPO_COMPACT_CONCURRENCY > 1 and keeps its stats/retention
    behavior."""
    from tempo_tpu.services.compactor import Compactor

    monkeypatch.setenv("TEMPO_COMPACT_CONCURRENCY", "4")
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "wal")),
                 backend=MemBackend())
    db.cfg.compaction.concat_small_input_bytes = 0
    db.cfg.compaction.min_input_blocks = 2
    db.cfg.compaction.retention_s = 10**9  # keep retention out of the sweep
    db.blocklist.update(TENANT, add=[
        build_block_from_traces(db.backend, TENANT, make_traces(15, seed=s))
        for s in (1, 2)])
    svc = Compactor(db)
    svc.run_once()
    assert svc.stats.errors == []
    assert svc.stats.blocks_compacted == 2
    assert all(m.compaction_level >= 1 for m in db.blocklist.metas(TENANT))
    db.close()


def test_local_backend_copy_object_hardlink(tmp_path):
    """The concat path's backend-side copy: content equal, and a
    subsequent overwrite of the SOURCE (tmp+rename) must not mutate the
    copy (immutability via inode sharing is safe only because writes
    replace directory entries)."""
    be = LocalBackend(str(tmp_path / "s"))
    be.write(TENANT, "blk-a", "data.vtpu", b"payload-1")
    n = be.copy_object(TENANT, "blk-a", "data.vtpu", "blk-b")
    assert n == len(b"payload-1")
    assert be.read(TENANT, "blk-b", "data.vtpu") == b"payload-1"
    be.write(TENANT, "blk-a", "data.vtpu", b"payload-2-replaced")
    assert be.read(TENANT, "blk-b", "data.vtpu") == b"payload-1"
    with pytest.raises(DoesNotExist):
        be.copy_object(TENANT, "blk-a", "missing", "blk-b")
