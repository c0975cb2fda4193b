"""Mesh-batched serving: one admission window -> all chips.

Three planes keep the new subsystem honest:
  * differential: batched-mesh windows (parallel/multiquery) must be
    bit-identical to the sequential single-chip engine over randomized
    window mixes -- mixed predicate shapes, struct/regex fallbacks,
    ragged block sizes;
  * comm accounting: the PR-10 jaxpr walker's per-collective bytes for
    the shrunk programs must equal a hand-computed ring-model
    expectation (costmodel.ring_wire_bytes), and the struct-op shrink
    must cut the per-node collective >= 5x;
  * fallbacks: TEMPO_BATCH=0, TEMPO_MESH_BATCH=0 and the no-mesh
    (single chip) executor all take the legacy paths byte for byte.
"""

from __future__ import annotations

import tempfile
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.db.search import SearchRequest, search_block
from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.testdata import make_traces

TENANT = "mesh-batch-t"

# eligible shapes (lower to predicate programs) + deliberate fallbacks
# (struct relation, regex, generic attr) -- a realistic window mix
_QUERIES = [
    '{ name = "db.query" }',
    '{ duration > 500ms }',
    '{ status = error && kind = server }',
    '{ name = "GET /api" || name = "cache.get" }',
    '{ span.http.status_code >= 500 }',
    '{ name = "db.query" && resource.service.name = "db" }',
    '{ name = "GET /" } >> { name = "db.query" }',   # struct: falls back
    '{ name =~ "GET .*" }',                          # regex: falls back
    '{ span.component = "grpc" }',                   # attr table: falls back
]


def _mkdb(**over) -> TempoDB:
    cfg = TempoDBConfig(
        wal_path=tempfile.mkdtemp(prefix="tempo-meshb-wal"),
        batch_window_ms=over.pop("batch_window_ms", 200.0),
        device_promote_touches=over.pop("device_promote_touches", 1),
        **over,
    )
    return TempoDB(cfg, backend=MemBackend())


def _dicts(resp):
    return [{**t.to_dict(), "matchedSpans": t.matched_spans} for t in resp.traces]


def test_mesh_batched_equals_sequential_randomized():
    """Randomized windows (3 seeds x ragged block sizes x shuffled query
    mixes) through the batching executor on the 8-device mesh: every
    result bit-identical to the sequential single-chip engine, and the
    mesh-batched route actually fired."""
    rng = np.random.default_rng(101)
    mesh0 = TEL.mesh_batch_stats()["launches"]
    for seed in (1, 2, 3):
        db = _mkdb()
        # ragged sizes: nothing aligns with the 8-way shard split
        n = int(rng.integers(40, 160))
        m = db.write_block(TENANT, make_traces(n, seed=seed, n_spans=int(rng.integers(3, 9))))
        blk = db.open_block(m)
        picks = [str(rng.choice(_QUERIES)) for _ in range(12)]
        reqs = [SearchRequest(query=q, limit=200) for q in picks]
        expected = [_dicts(search_block(blk, r)) for r in reqs]
        # a leader holds its window open only when another submitter is
        # already inside the executor: on a loaded box (other xdist
        # workers) every thread of a burst can arrive alone, so a burst
        # that formed no group is sent again, a bounded number of times
        for _ in range(5):
            with ThreadPoolExecutor(len(reqs)) as ex:
                futs = [ex.submit(db.search_blocks, TENANT, [m], r) for r in reqs]
                got = [_dicts(f.result()) for f in futs]
            for q, e, g in zip(picks, expected, got):
                assert e == g, f"mesh-batched != sequential for {q!r} (seed {seed})"
            if TEL.mesh_batch_stats()["launches"] > mesh0:
                break
        db.close()
    assert TEL.mesh_batch_stats()["launches"] > mesh0, \
        "no window ever took the mesh-batched route"


def test_mesh_kernel_bit_identity_direct():
    """Kernel-level differential: the shard_map multiquery program's
    (trace_mask, counts) equal the single-chip fused interpreter's bit
    for bit, across program shapes and window occupancies."""
    from tempo_tpu.db.search import _plan_for_block
    from tempo_tpu.ops.filter import required_columns
    from tempo_tpu.ops.multiquery import (
        _p2,
        eval_multiquery,
        lower_plan,
        pack_queries,
    )
    from tempo_tpu.ops.stage import stage_block
    from tempo_tpu.parallel import make_mesh
    from tempo_tpu.parallel.multiquery import (
        mesh_batch_eligible,
        mesh_eval_multiquery,
    )

    mesh = make_mesh(8)
    db = _mkdb()
    m = db.write_block(TENANT, make_traces(130, seed=17, n_spans=7))
    blk = db.open_block(m)
    by_shape: dict = {}
    planned_of: dict = {}
    for q in _QUERIES:
        p = _plan_for_block(blk, SearchRequest(query=q))
        if p.prune:
            continue
        lq = lower_plan(p)
        if lq is None:
            continue  # fallback queries are covered by the db-level test
        by_shape.setdefault(lq.shape, []).append(lq)
        planned_of.setdefault(lq.shape, p)
    assert by_shape, "no eligible programs lowered"
    for shape, lqs in by_shape.items():
        needed = required_columns(planned_of[shape].conds) + \
            list(planned_of[shape].extra_cols)
        staged = stage_block(blk, needed + ["trace.start_ms"])
        q_b = _p2(len(lqs), lo=1)
        progs = pack_queries(lqs, q_b)
        tm1, c1 = eval_multiquery(lqs, staged, progs)
        assert mesh_batch_eligible(mesh, staged)
        tm2, c2 = mesh_eval_multiquery(mesh, lqs, staged, progs)
        np.testing.assert_array_equal(np.asarray(tm1), tm2)
        np.testing.assert_array_equal(np.asarray(c1), c2)
    db.close()


def _struct_cols(rng, B, S, NT, orphan_rate=0.05):
    """Stacked struct-query columns with parent chains AND orphans
    (pid == -2) scattered over EVERY sp shard."""
    cols = {
        "span.trace_sid": np.sort(
            rng.integers(0, NT, size=(B, S)).astype(np.int32), axis=1),
        "span.dur_us": rng.integers(0, 1000, size=(B, S)).astype(np.int32),
        "span.parent_idx": np.full((B, S), -1, np.int32),
    }
    for b in range(B):
        sid = cols["span.trace_sid"][b]
        prev_same = np.zeros(S, bool)
        prev_same[1:] = sid[1:] == sid[:-1]
        link = prev_same & (rng.random(S) < 0.5)
        pidx = np.where(link, np.arange(S) - 1, -1).astype(np.int32)
        pidx[rng.random(S) < orphan_rate] = -2
        cols["span.parent_idx"][b] = pidx
    return cols


def test_struct_shrink_bit_identical_and_5x_per_node(monkeypatch):
    """The hoisted + bit-packed struct collectives return byte-identical
    results to the legacy per-node triple gather for every relation, and
    the walker-priced per-node collective shrinks >= 5x (the ISSUE
    acceptance: the '>' node's 6S-byte gather set becomes one packed
    S/8-byte gather)."""
    from tempo_tpu.ops.filter import Cond, Operands, T_SPAN
    from tempo_tpu.parallel import make_mesh
    from tempo_tpu.parallel.search import sharded_search
    from tempo_tpu.util import costmodel

    mesh = make_mesh(8)
    rng = np.random.default_rng(7)
    B, S, NT = 2, 2048, 64  # unique span bucket: keys the walker rows
    cols = _struct_cols(rng, B, S, NT)
    n_spans = np.asarray([S, S - 137], np.int32)
    conds = (Cond(target=T_SPAN, col="span.dur_us", op="lt"),
             Cond(target=T_SPAN, col="span.dur_us", op="ge"))
    operands = Operands.build([(0, 800, 0, 0.0, 0.0), (0, 100, 0, 0.0, 0.0)])
    # '>' LAST: the walker keeps one row per (op, bucket), last capture
    # wins -- ordering leaves the parent-relation node (the common
    # production shape, and the one the >= 5x criterion prices) in the
    # walker rows for both variants
    for op in ("~", ">>", ">"):
        tree = ("struct", op, ("cond", 0), ("cond", 1))
        monkeypatch.setenv("TEMPO_STRUCT_PACK", "1")
        tm1, sc1 = sharded_search(mesh, tree, conds, operands, cols,
                                  n_spans, nt=NT)
        monkeypatch.setenv("TEMPO_STRUCT_PACK", "0")
        tm0, sc0 = sharded_search(mesh, tree, conds, operands, cols,
                                  n_spans, nt=NT)
        np.testing.assert_array_equal(tm1, tm0, err_msg=f"struct {op}")
        np.testing.assert_array_equal(sc1, sc0, err_msg=f"struct {op}")
    assert costmodel.COST.drain(30.0)
    packed = costmodel.COST.comm_for("mesh_search", str(S))
    legacy = costmodel.COST.comm_for("mesh_search_nopack", str(S))
    assert packed.get("all_gather", 0) > 0 and legacy.get("all_gather", 0) > 0
    shrink = legacy["all_gather"] / packed["all_gather"]
    assert shrink >= 5.0, (legacy, packed)
    # psum (the per-trace combine) is untouched by the shrink
    assert packed["psum"] == legacy["psum"]


def test_walker_comm_equals_ring_model():
    """Hand-computed ring-model expectation vs the jaxpr walker, for the
    SHRUNK programs: the packed '>' struct search and the batched
    multiquery launch. Exact byte equality -- the cross-check that the
    static pricing and the program shapes agree."""
    from tempo_tpu.db.search import _plan_for_block
    from tempo_tpu.ops.filter import Cond, Operands, T_SPAN, required_columns
    from tempo_tpu.ops.multiquery import _p2, lower_plan, pack_queries
    from tempo_tpu.ops.stage import stage_block
    from tempo_tpu.parallel import make_mesh
    from tempo_tpu.parallel.multiquery import mesh_eval_multiquery
    from tempo_tpu.parallel.search import sharded_search
    from tempo_tpu.util import costmodel
    from tempo_tpu.util.costmodel import ring_wire_bytes

    mesh = make_mesh(8)
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]

    # --- packed struct '>' search: one bit-packed lhs gather + the
    # per-trace psum stitch
    rng = np.random.default_rng(11)
    B, S, NT = 2, 4096, 128  # unique bucket for this test's walker rows
    cols = _struct_cols(rng, B, S, NT)
    n_spans = np.asarray([S, S - 99], np.int32)
    conds = (Cond(target=T_SPAN, col="span.dur_us", op="lt"),
             Cond(target=T_SPAN, col="span.dur_us", op="ge"))
    operands = Operands.build([(0, 900, 0, 0.0, 0.0), (0, 50, 0, 0.0, 0.0)])
    sharded_search(mesh, ("struct", ">", ("cond", 0), ("cond", 1)),
                   conds, operands, cols, n_spans, nt=NT)
    assert costmodel.COST.drain(30.0)
    got = costmodel.COST.comm_for("mesh_search", str(S))
    Bl = B // dp
    expected = {
        # packed lhs mask: out aval (Bl, S/8) uint8, k=sp, dp groups
        "all_gather": ring_wire_bytes("all_gather", 0, Bl * (S // 8), sp) * dp,
        # seg_reduce count stitch: (Bl, NT) int32. The trace carries TWO
        # psum eqns (the tracify fold and the reporting fold over the
        # same mask) that XLA CSEs into one -- the static walker prices
        # the jaxpr, so the model expects both (a deliberate
        # conservative overcount, never an undercount)
        "psum": 2 * ring_wire_bytes("psum", Bl * NT * 4, Bl * NT * 4, sp) * dp,
    }
    assert got == expected, (got, expected)

    # --- batched multiquery: exactly ONE psum for the whole window,
    # (q_b, NG+1, NT) int32 partial counts over every device
    db = _mkdb()
    m = db.write_block(TENANT, make_traces(90, seed=29, n_spans=6))
    blk = db.open_block(m)
    p = _plan_for_block(blk, SearchRequest(query='{ duration > 100ms }'))
    lqs = [lower_plan(p)] * 3
    q_b = _p2(3, lo=1)
    progs = pack_queries(lqs, q_b)
    needed = required_columns(p.conds) + list(p.extra_cols)
    staged = stage_block(blk, needed + ["trace.start_ms"])
    mesh_eval_multiquery(mesh, lqs, staged, progs)
    assert costmodel.COST.drain(30.0)
    got_mq = costmodel.COST.comm_for("mesh_multiquery", str(staged.n_spans_b))
    ng1 = lqs[0].shape.n_groups_b + 1
    in_b = q_b * ng1 * staged.n_traces_b * 4
    assert got_mq == {"psum": ring_wire_bytes("psum", in_b, in_b, dp * sp)}, \
        (got_mq, {"q_b": q_b, "ng1": ng1, "nt": staged.n_traces_b})
    db.close()


def test_fallback_paths_byte_identical(monkeypatch):
    """TEMPO_BATCH=0 (no executor), TEMPO_MESH_BATCH=0 (single-chip
    fused launch) and a no-mesh executor must all return byte-identical
    results -- the legacy paths are untouched by the mesh route."""
    from tempo_tpu.db.batchexec import batched_search_block_many

    traces = make_traces(110, seed=23, n_spans=6)
    req = SearchRequest(query='{ duration > 50ms && status != error }',
                        limit=200)

    # reference: batching executor disabled end to end
    monkeypatch.setenv("TEMPO_BATCH", "0")
    db0 = _mkdb()
    m0 = db0.write_block(TENANT, traces)
    assert not db0.batchers.enabled
    ref = _dicts(db0.search_blocks(TENANT, [m0], req))
    assert ref == _dicts(search_block(db0.open_block(m0), req))
    db0.close()
    monkeypatch.delenv("TEMPO_BATCH")

    # mesh batching pinned off: window leaders keep the single-chip
    # fused launch; results identical
    monkeypatch.setenv("TEMPO_MESH_BATCH", "0")
    r0 = TEL.routing_counts()
    db1 = _mkdb()
    m1 = db1.write_block(TENANT, traces)
    blk1 = db1.open_block(m1)
    outs = batched_search_block_many(
        db1.batchers.search, [(blk1, req, None)] * 4)
    for o in outs:
        assert _dicts(o) == ref
    r1 = TEL.routing_counts()
    assert r1.get(("search_batch", "mesh", "mesh_batched"), 0) == \
        r0.get(("search_batch", "mesh", "mesh_batched"), 0)
    assert r1.get(("search_batch", "device", "coalesced"), 0) > \
        r0.get(("search_batch", "device", "coalesced"), 0)
    db1.close()
    monkeypatch.delenv("TEMPO_MESH_BATCH")

    # single-chip executor (mesh_fn yields nothing): same story
    from tempo_tpu.db.batchexec import QueryBatchers

    db2 = _mkdb()
    m2 = db2.write_block(TENANT, traces)
    blk2 = db2.open_block(m2)
    db2.batchers = QueryBatchers(enabled=True, window_ms=200.0,
                                 mesh_fn=lambda: None)
    outs2 = batched_search_block_many(
        db2.batchers.search, [(blk2, req, None)] * 4)
    for o in outs2:
        assert _dicts(o) == ref
    db2.close()
