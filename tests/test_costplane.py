"""Device cost observability plane (util/costmodel + util/costledger).

Covers the acceptance surface end to end: XLA program-cost capture on
the CPU backend (skip-gated -- some backends return no cost analysis),
EXACT comm-walker byte counts on a synthetic shard_map program against
the documented ring model, HBM-ledger reconciliation vs the staged
cache's and live stager's own accounting, CostLedger round-trip +
corrupt-artifact fallback, ledger-backed `auto` find routing and
live-engine crossover seeding (env override wins), the struct-node
budget replication fix, and the /status/cost + /metrics surfaces of a
running app.
"""

from __future__ import annotations

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from tempo_tpu.util import costledger
from tempo_tpu.util.costmodel import COST, collective_comm_bytes
from tempo_tpu.util.kerneltel import TEL

TENANT = "cost-t"


@pytest.fixture(autouse=True)
def _fresh_plane():
    TEL.reset()  # also resets COST (launch/program tables)
    costledger.reset_for_tests()
    yield
    TEL.reset()
    costledger.reset_for_tests()


def _padded_filter_eval():
    """One tiny filter-kernel launch (padded to the 1024 floor)."""
    from tempo_tpu.ops.device import PAD_I32, pad_rows
    from tempo_tpu.ops.filter import Cond, Operands, T_SPAN, eval_block

    N, NB = 64, 1024
    cols = {
        "span.trace_sid": pad_rows(np.zeros(N, np.int32), NB, PAD_I32),
        "span.dur_us": pad_rows(np.arange(N, dtype=np.int32), NB, PAD_I32),
        "trace.span_off": pad_rows(np.asarray([0, N], np.int32), NB + 1,
                                   np.int32(N)),
    }
    conds = (Cond(target=T_SPAN, col="span.dur_us", op="ge"),)
    ops = Operands.build([(0, 10, 0, 0.0, 0.0)])
    return eval_block((("cond", 0), conds), cols, ops, N, 1, NB, NB, NB)


# ------------------------------------------------------- program capture


def test_cost_capture_filter_on_cpu():
    """A new filter compile lands a background cost-analysis row keyed
    (op, bucket): FLOPs + bytes accessed from XLA itself, peak temp
    from memory_analysis."""
    _padded_filter_eval()
    assert COST.drain(30), "cost capture worker did not drain"
    table = COST.program_table()
    row = table.get(("filter", "1024"))
    assert row is not None, sorted(table)
    if row["error"]:
        pytest.skip(f"cost analysis unavailable on this backend: {row['error']}")
    assert row["flops"] > 0
    assert row["bytes_accessed"] > 0
    assert row["launches"] >= 1
    # second launch of the same program: cache hit, no new capture, but
    # the launch counter moves
    _padded_filter_eval()
    assert COST.program_table()[("filter", "1024")]["launches"] >= 2


def test_reset_releases_pending_captures():
    """reset() with capture specs still queued must release their
    pending counts -- a wedged counter would make every later drain()
    (and /status/cost) wait its full timeout forever."""
    from tempo_tpu.util.costmodel import ProgramSpec

    COST.enqueue("x", "1", ProgramSpec(None, (), {}, None, 1))
    COST.reset()
    assert COST.drain(5.0), "drain wedged after reset with queued captures"
    # the worker itself survives a broken spec (whichever side of the
    # race it landed on) and keeps serving later captures
    _padded_filter_eval()
    assert COST.drain(30)
    assert ("filter", "1024") in COST.program_table()


def test_comm_walker_exact_bytes_on_synthetic_shard_map():
    """The documented ring model, checked to the byte on a hand-built
    shard_map program over the 8-device mesh (dp=2 x sp=4)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from tempo_tpu.parallel.mesh import make_mesh, smap

    mesh = make_mesh(8)  # dp 2 x sp 4
    k = mesh.shape["sp"]
    groups = mesh.devices.size // k  # independent sp-groups (= dp)
    assert (k, groups) == (4, 2)

    def local(x):
        g = jax.lax.all_gather(x, "sp", axis=0, tiled=True)
        s = jax.lax.psum(x, "sp")
        r = jax.lax.psum_scatter(x, "sp", scatter_dimension=0, tiled=True)
        return g.sum() + s.sum() + r.sum()

    fn = jax.jit(smap(local, mesh, in_specs=(P("sp"),), out_specs=P()))
    x = jax.ShapeDtypeStruct((16, 8), np.dtype(np.float32))  # shard (4, 8)
    jaxpr = jax.make_jaxpr(fn)(x)
    comm = collective_comm_bytes(jaxpr, dict(mesh.shape), mesh.devices.size)
    shard_bytes = 4 * 8 * 4  # (4, 8) f32 per sp-shard
    full_bytes = 16 * 8 * 4  # gathered (16, 8) f32
    assert comm == {
        "all_gather": full_bytes * (k - 1) * groups,       # 3072
        "psum": 2 * shard_bytes * (k - 1) * groups,        # 1536
        "reduce_scatter": shard_bytes * (k - 1) * groups,  # 768
    }


def test_comm_walker_counts_struct_all_gathers():
    """Cross-check of the struct budget term on the SHRUNK program:
    one (bit-packed) lhs-mask all_gather per struct node, plus one
    hoisted parent + validity gather pair per launch when any '>>'/'~'
    node needs the replicated parent table ('>' runs off the local
    parent column) -- the replication _stacked_words_est prices."""
    import jax

    from tempo_tpu.db.search import _count_struct_nodes
    from tempo_tpu.ops.filter import Cond, T_SPAN, normalize_tree
    from tempo_tpu.parallel.mesh import make_mesh
    from tempo_tpu.parallel.search import make_sharded_search

    mesh = make_mesh(8)
    conds = (Cond(target=T_SPAN, col="span.name_id", op="eq"),
             Cond(target=T_SPAN, col="span.name_id", op="eq"))
    one = ("struct", ">", ("cond", 0), ("cond", 1))
    two = ("struct", ">>", one, ("cond", 1))
    assert _count_struct_nodes(one) == 1
    assert _count_struct_nodes(two) == 2

    def count_gathers(tree):
        names = ("span.name_id", "span.parent_idx", "span.trace_sid",
                 "trace.span_off")
        fn = make_sharded_search(mesh, normalize_tree(tree, conds), conds,
                                 tuple(sorted(names)), 8, 32, 1, 8)
        avals = [jax.ShapeDtypeStruct(s, np.dtype(np.int32)) for s in
                 [(8, 2, 3), (8, 2, 2), (8,)]]
        col_avals = []
        for n in sorted(names):
            shape = (8, 9) if n == "trace.span_off" else (
                (8, 8) if n.startswith("trace.") else (8, 32))
            col_avals.append(jax.ShapeDtypeStruct(shape, np.dtype(np.int32)))
        # float operands ride aval slot 1 as f32
        avals[1] = jax.ShapeDtypeStruct((8, 2, 2), np.dtype(np.float32))
        jaxpr = jax.make_jaxpr(fn)(*avals, *col_avals)

        def walk(jx):
            n = 0
            for eqn in jx.eqns:
                if eqn.primitive.name == "all_gather":
                    n += 1
                for v in eqn.params.values():
                    if hasattr(v, "eqns"):
                        n += walk(v)
                    elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                        n += walk(v.jaxpr)
            return n

        return walk(jaxpr.jaxpr)

    # '>' alone: just its packed lhs mask
    assert count_gathers(one) == 1
    # '>' nested under '>>': two per-node masks + the once-per-launch
    # hoisted pid + packed-validity pair
    assert count_gathers(two) == 4


def test_struct_budget_scales_with_node_count(monkeypatch):
    """The pre-IO stacked estimate grows per additional struct node --
    the regression the eval_shard budget fix closes (one node used to
    price a whole chain). Post-shrink pricing: S_b*sp per node (the
    replicated mask) + 4*S_b*sp once when the added node is a '>>'/'~'
    (the hoisted parent/validity tables and closure temps). With the
    TEMPO_STRUCT_PACK=0 escape hatch the budget must price the legacy
    triple-gather program (6*S_b*sp per node) -- what will actually
    run on device."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import (
        SearchRequest,
        _plan_for_block,
        _stacked_words_est,
    )
    from tempo_tpu.util.testdata import make_traces

    import tempfile

    db = TempoDB(TempoDBConfig(wal_path=tempfile.mkdtemp(prefix="cost-w")),
                 backend=MemBackend())
    db.write_block(TENANT, make_traces(30, seed=5, n_spans=6))
    blk = db.open_block(db.blocklist.metas(TENANT)[0])

    def est_for(query):
        p = _plan_for_block(blk, SearchRequest(query=query))
        assert p.has_struct and not p.prune
        from tempo_tpu.ops.filter import required_columns

        needed = [n for n in required_columns(p.conds) + list(p.extra_cols)
                  if not n.startswith("span@")]
        return _stacked_words_est([(blk, p)], needed, p.tree, sp=4,
                                  S_b=4096, NT_b=1024, attr_b={})

    e1 = est_for('{ name = "GET /api" } > { true }')
    e2 = est_for('{ name = "GET /api" } > { true } >> { name = "db.query" }')
    # the added '>>' node: one more replicated mask + the hoisted tables
    assert e2 - e1 == (1 + 4) * 4096 * 4
    monkeypatch.setenv("TEMPO_STRUCT_PACK", "0")
    l1 = est_for('{ name = "GET /api" } > { true }')
    l2 = est_for('{ name = "GET /api" } > { true } >> { name = "db.query" }')
    assert l2 - l1 == 6 * 4096 * 4  # legacy: lm/pid/valid + temps per node
    assert l1 - e1 == 5 * 4096 * 4  # one '>' node: 6x legacy vs 1x packed
    db.close()


# ------------------------------------------------------------ HBM ledger


def test_hbm_ledger_reconciles_staged_and_livestage(tmp_path):
    """The ledger's components must equal the subsystems' own books:
    staged_cache bytes == ops/stage's LRU accounting, livestage bytes ==
    the stagers' resident device arrays."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.ops.livestage import LiveStager, stager_device_bytes
    from tempo_tpu.ops.stage import stage_block, staged_cache_stats
    from tempo_tpu.util.testdata import make_traces

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")),
                 backend=MemBackend())
    db.write_block(TENANT, make_traces(40, seed=7, n_spans=5))
    blk = db.open_block(db.blocklist.metas(TENANT)[0])
    staged = stage_block(blk, ["span.dur_us", "trace.start_ms"])
    assert staged.cols

    hbm = COST.hbm_snapshot()
    st = staged_cache_stats()
    assert hbm["components"]["staged_cache"]["bytes"] == st["bytes"] > 0
    assert hbm["accounted_bytes"] >= st["bytes"]

    # livestage component: a stager with resident device columns
    stager = LiveStager()
    stager._dev = {"alive": np.zeros(64, np.int32)}  # stand-in resident col
    total, n = stager_device_bytes()
    assert total >= stager.device_bytes() == 64 * 4
    hbm2 = COST.hbm_snapshot()
    assert hbm2["components"]["livestage"]["bytes"] == total
    assert hbm2["components"]["livestage"]["stagers"] == n
    db.close()


# ------------------------------------------------------------ CostLedger


def test_cost_ledger_roundtrip_and_atomic_publish(tmp_path):
    path = str(tmp_path / "ledger.json")
    led = costledger.CostLedger(path)
    led.update("find", winner="device", host_s=0.01, device_s=0.002)
    assert led.publish()
    # a fresh loader sees exactly what was published
    led2 = costledger.CostLedger(path)
    e = led2.get("find")
    assert e["winner"] == "device" and e["device_s"] == 0.002
    assert e["measured_at_unix"] > 0
    assert led2.load_error == ""
    # updates merge rather than replace
    led2.update("find", crossover_rows=123.0)
    assert led2.get("find")["winner"] == "device"
    assert led2.get("find")["crossover_rows"] == 123.0


def test_cost_ledger_corrupt_artifact_falls_back_empty(tmp_path, capsys):
    path = tmp_path / "ledger.json"
    path.write_text("{not json")
    led = costledger.CostLedger(str(path))
    assert led.load_error
    assert led.entries() == {}
    assert "unreadable" in capsys.readouterr().err
    # wrong shape is also corrupt, not a crash
    path.write_text(json.dumps({"entries": [1, 2]}))
    led = costledger.CostLedger(str(path))
    assert led.load_error and led.entries() == {}
    # the next publish rewrites the artifact whole and recovers
    led.update("find", winner="host")
    assert led.publish()
    assert costledger.CostLedger(str(path)).get("find")["winner"] == "host"


# ---------------------------------------------------- ledger-backed find


def _two_tiny_blocks(tmp_path):
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.util.testdata import make_traces

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")),
                 backend=MemBackend())
    ids = []
    for seed in (1, 2):
        traces = make_traces(32, seed=seed, n_spans=3)
        db.write_block(TENANT, traces)
        ids += [tid for tid, _ in traces]
    blocks = [db.open_block(m) for m in db.blocklist.metas(TENANT)]
    return db, blocks, ids


def test_find_auto_policy_routes_from_ledger(tmp_path, monkeypatch):
    """auto on one chip: no ledger entry = the host default
    (single_chip_rtt); a committed device winner = device with reason
    ledger_crossover; TEMPO_FIND_MODE env still wins over everything.
    Results are bit-identical on every path."""
    from tempo_tpu.block import schema as S
    from tempo_tpu.ops import find as find_mod

    costledger.configure(str(tmp_path / "ledger.json"))
    monkeypatch.setattr(find_mod, "_n_devices", lambda: 1)
    db, blocks, ids = _two_tiny_blocks(tmp_path)
    q = np.asarray([S.trace_id_to_codes(ids[0].rjust(16, b"\x00")),
                    S.trace_id_to_codes(ids[-1].rjust(16, b"\x00"))], np.int32)

    def routed(mode):
        r0 = TEL.routing_counts()
        out = find_mod.lookup_ids_blocks_cached(blocks, q, mode=mode)
        r1 = TEL.routing_counts()
        hit = [k for k, n in r1.items() if k[0] == "find" and n > r0.get(k, 0)]
        assert len(hit) == 1, hit
        return out, hit[0]

    base, key = routed("auto")
    assert key[1:] == ("host", "single_chip_rtt")

    costledger.ledger().update(costledger.KEY_FIND, winner="device")
    dev, key = routed("auto")
    assert key[1:] == ("device", "ledger_crossover")
    np.testing.assert_array_equal(dev, base)

    costledger.ledger().update(costledger.KEY_FIND, winner="host")
    host, key = routed("auto")
    assert key[1:] == ("host", "ledger_crossover")
    np.testing.assert_array_equal(host, base)

    # a committed crossover_rows beats the binary winner: routing
    # compares THIS batch's id rows (64 here) against it
    costledger.ledger().update(costledger.KEY_FIND, crossover_rows=1.0)
    dev2, key = routed("auto")
    assert key[1:] == ("device", "ledger_crossover")
    np.testing.assert_array_equal(dev2, base)
    costledger.ledger().update(costledger.KEY_FIND, crossover_rows=1e9)
    _, key = routed("auto")
    assert key[1:] == ("host", "ledger_crossover")

    monkeypatch.setenv("TEMPO_FIND_MODE", "host")
    _, key = routed("device")  # env beats even an explicit caller mode
    assert key[1:] == ("host", "forced")
    db.close()


def test_calibrate_find_commits_ledger_entry(tmp_path):
    from tempo_tpu.ops.find import calibrate_find

    costledger.configure(str(tmp_path / "ledger.json"))
    db, blocks, _ = _two_tiny_blocks(tmp_path)
    idx = blocks[0].trace_index["trace.id_codes"]
    q = np.asarray(idx[:8], np.int32)
    entry = calibrate_find(blocks, q, repeats=1)
    assert entry["winner"] in ("host", "device")
    assert entry["host_s"] > 0 and entry["device_s"] > 0
    assert entry["rows"] == sum(
        b.trace_index["trace.id_codes"].shape[0] for b in blocks)
    # persisted: a fresh loader (new process stand-in) sees the race
    fresh = costledger.CostLedger(str(tmp_path / "ledger.json"))
    assert fresh.get(costledger.KEY_FIND)["winner"] == entry["winner"]
    db.close()


# ----------------------------------------------- live-engine ledger seed


def test_live_engine_seeds_from_ledger_env_wins(tmp_path, monkeypatch):
    from tempo_tpu.db.live_engine import LiveEngine

    costledger.configure(str(tmp_path / "ledger.json"))
    costledger.ledger().update(costledger.KEY_LIVE_SEARCH,
                               host_s_per_row=1e-6, device_fixed_s=0.01)
    monkeypatch.delenv("TEMPO_LIVE_CROSSOVER_ROWS", raising=False)
    eng = LiveEngine(instance=None)
    assert eng._host_s_per_row == 1e-6
    assert eng._dev_fixed_s == 0.01
    assert eng.crossover_rows() == pytest.approx(10000.0)
    assert eng._route(20000)[0] == "device"
    assert eng._route(100) == ("host", "tiny_head")

    # env seed wins: ledger values must NOT preload the EMAs
    monkeypatch.setenv("TEMPO_LIVE_CROSSOVER_ROWS", "123")
    eng2 = LiveEngine(instance=None)
    assert eng2._host_s_per_row is None and eng2._dev_fixed_s is None
    assert eng2.crossover_rows() == 123.0

    # a purely ledger-seeded engine must NOT re-publish (a restart loop
    # would keep refreshing measured_at_unix on rates it never measured)
    monkeypatch.delenv("TEMPO_LIVE_CROSSOVER_ROWS", raising=False)
    eng3 = LiveEngine(instance=None)
    eng3.persist_crossover()
    assert costledger.CostLedger(
        str(tmp_path / "ledger.json")).get(costledger.KEY_LIVE_SEARCH) is None

    # write-back: measured EMAs persist for the next process
    eng._observe_engine("host", 1000, 0.002)
    eng._observe_engine("device", 1000, 0.05)
    eng.persist_crossover()
    fresh = costledger.CostLedger(str(tmp_path / "ledger.json"))
    e = fresh.get(costledger.KEY_LIVE_SEARCH)
    assert e["host_s_per_row"] > 0 and e["device_fixed_s"] > 0
    assert e["crossover_rows"] > 0


def test_host_rate_seed_from_ledger(tmp_path, monkeypatch):
    from tempo_tpu.db import route as route_mod

    costledger.configure(str(tmp_path / "ledger.json"))
    costledger.ledger().update(costledger.KEY_BLOCK_SCAN,
                               host_rate_bps=9.9e9)
    monkeypatch.setattr(route_mod, "_HOST_RATE_SEEDED", False)
    monkeypatch.setattr(route_mod, "_HOST_RATE_BPS", 1.5e9)
    route_mod.seed_host_rate_from_ledger()
    assert route_mod._HOST_RATE_BPS == 9.9e9
    # idempotent: a second call (another TempoDB) never re-seeds over
    # the EMA the process has been learning since
    route_mod.note_host_rate(100 << 20, 0.01)
    learned = route_mod._HOST_RATE_BPS
    route_mod.seed_host_rate_from_ledger()
    assert route_mod._HOST_RATE_BPS == learned


# --------------------------------------------------- app status surfaces


def test_status_cost_endpoint_and_metrics_families(tmp_path):
    """Drive the filter, find, timeseries and mesh-search programs, then
    read /status/cost off a running app: per-(op,bucket) rows with
    FLOPs/bytes (+ utilization fields once measured calls exist),
    per-collective comm bytes for the mesh program, the HBM ledger and
    the ledger/compile-cache sections; /metrics still passes the strict
    OpenMetrics parse with the new families present."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest
    from tempo_tpu.ops.filter import Operands
    from tempo_tpu.ops.stage import stage_block
    from tempo_tpu.ops.timeseries import eval_timeseries_device
    from tempo_tpu.services.app import App, AppConfig
    from tempo_tpu.services.ingester import IngesterConfig
    from tempo_tpu.util.testdata import make_traces

    from test_observability import _free_port, parse_openmetrics_strict

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")),
                 backend=MemBackend())
    for seed in (1, 2):
        db.write_block(TENANT, make_traces(24, seed=seed, n_spans=4))
    metas = db.blocklist.metas(TENANT)
    req = SearchRequest(tags={"k8s.cluster.name": "prod"}, limit=5)
    for _ in range(3):
        db.search_blocks(TENANT, metas, req)  # 8 cpu devices -> mesh path
    _padded_filter_eval()  # the single-chip filter kernel
    blk = db.open_block(metas[0])
    # find: batched device bisection
    from tempo_tpu.ops.find import lookup_ids_blocks

    lookup_ids_blocks([blk.trace_index["trace.id_codes"]],
                      np.asarray(blk.trace_index["trace.id_codes"][:4],
                                 np.int32))
    # timeseries: one fused device fold over a staged block
    staged = stage_block(blk, ["span.start_ms"], cache=False)
    eval_timeseries_device((None, ()), staged, Operands.build([]),
                           gid=np.zeros(staged.n_spans, np.int32),
                           val=None, vpres=None, t0_rel_ms=0, step_ms=1000,
                           n_buckets=4, n_groups=1)
    assert COST.drain(60)

    cfg = AppConfig(
        storage_path=str(tmp_path / "store"), http_port=_free_port(),
        compaction_cycle_s=9999,
        ingester=IngesterConfig(max_trace_idle_s=9999, max_block_age_s=9999,
                                flush_check_period_s=9999))
    app = App(cfg)
    try:
        app.start()
        app.serve_http(background=True)
        base = f"http://127.0.0.1:{cfg.http_port}"
        with urllib.request.urlopen(base + "/status/cost", timeout=10) as r:
            cost = json.load(r)
        ops_seen = {p["op"] for p in cost["programs"]}
        assert {"filter", "find", "timeseries", "mesh_search"} <= ops_seen, ops_seen
        for p in cost["programs"]:
            if p["op"] == "filter":
                assert p["flops"] > 0 and p["bytes_accessed"] > 0
        mesh_rows = [p for p in cost["programs"] if p["op"] == "mesh_search"]
        assert any(p.get("comm_bytes_per_launch") for p in mesh_rows)
        assert any(c["op"] == "mesh_search" and c["bytes_total"] > 0
                   for c in cost["comm"])
        assert "staged_cache" in cost["hbm"]["components"]
        assert "entries" in cost["ledger"]
        assert {"enabled", "dir", "disk_hits"} <= set(cost["compile_cache"])

        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        fams = parse_openmetrics_strict(text)
        assert "tempo_program_flops" in fams
        assert "tempo_program_bytes_accessed" in fams
        assert "tempo_mesh_comm_bytes" in fams
        assert "tempo_hbm_bytes" in fams
    finally:
        app.stop()
        db.close()


def test_compile_cache_counts_disk_hits(tmp_path, monkeypatch):
    """Enabling the persistent cache registers the jax.monitoring
    listener; clearing the in-process jit caches and re-running the
    same program must deserialize from disk and count a hit -- the
    counter that splits restart-warm compiles from fresh XLA work."""
    import jax

    from tempo_tpu.util import costmodel

    monkeypatch.delenv(costmodel.JAX_CACHE_ENV, raising=False)
    jax.config.update("jax_enable_compilation_cache", True)  # off in tests
    assert costmodel.enable_compile_cache(str(tmp_path / "cc"))
    try:
        h0 = costmodel.compile_cache_stats()["disk_hits"]

        @jax.jit
        def f(x):
            return x * 3 + 1

        f(np.arange(8, dtype=np.float32))
        jax.clear_caches()  # a restart stand-in: jit cache gone, disk not
        f(np.arange(8, dtype=np.float32))
        st = costmodel.compile_cache_stats()
        assert st["enabled"] and st["dir"]
        assert st["disk_hits"] > h0, st
    finally:
        # tmp_path is reaped: the rest of the suite must not keep
        # reading a vanishing cache dir
        jax.config.update("jax_enable_compilation_cache", False)
        costmodel.disable_compile_cache()
        assert not costmodel.compile_cache_stats()["enabled"]
