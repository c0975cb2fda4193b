"""Multi-chip sharding tests on the 8-virtual-device CPU mesh.

Each sharded kernel is checked against its single-device oracle
(ops/find.py, ops/bloom_ops.py, numpy) to prove the collectives combine
results identically to the host-side merge they replace."""

import numpy as np
import pytest

from tempo_tpu.block import schema as S
from tempo_tpu.block.bloom import ShardedBloom
from tempo_tpu.ops.device import bucket, pad_rows
from tempo_tpu.ops.filter import Cond, Operands, T_RES, T_SPAN
from tempo_tpu.ops.find import lookup_ids
from tempo_tpu.parallel import (
    distributed_query_step,
    make_mesh,
    sharded_bloom_union,
    sharded_find,
    sharded_search,
)
from tempo_tpu.util.testdata import make_traces


@pytest.fixture(scope="module")
def mesh():
    m = make_mesh(8)
    assert m.shape == {"dp": 2, "sp": 4}
    return m


def _id_codes(traces):
    return np.asarray(
        sorted(S.trace_id_to_codes(tid) for tid, _ in traces), dtype=np.int32
    )


def test_sharded_find_matches_per_block(mesh):
    rng = np.random.default_rng(7)
    blocks = []
    all_ids = []
    for b in range(5):  # deliberately not a multiple of 8 -> pad blocks
        traces = make_traces(30 + 7 * b, seed=b, n_spans=1)
        codes = _id_codes(traces)
        blocks.append(codes)
        all_ids.extend(map(tuple, codes))
    # queries: every 3rd real id + 4 misses
    queries = np.asarray(all_ids[::3], dtype=np.int32)
    misses = np.asarray(
        [S.trace_id_to_codes(bytes([i]) * 16) for i in (1, 2, 254, 255)], dtype=np.int32
    )
    queries = np.concatenate([queries, misses])

    out = sharded_find(mesh, blocks, queries)

    for qi, q in enumerate(queries):
        expected = []
        for bi, codes in enumerate(blocks):
            sid = lookup_ids(codes, q[None, :])[0]
            if sid >= 0:
                expected.append((bi, sid))
        blk, row = out[qi]
        if not expected:
            assert blk == -1 and row == -1
        else:
            assert (blk, row) in expected


def test_sharded_search_matches_oracle(mesh):
    rng = np.random.default_rng(3)
    dp, sp = 2, 4
    B, S_rows, NT, R = 4, 64, 16, 8
    cols = {
        "span.trace_sid": rng.integers(0, NT, size=(B, S_rows)).astype(np.int32),
        "span.dur_us": rng.integers(0, 1000, size=(B, S_rows)).astype(np.int32),
        "span.res_idx": rng.integers(0, R, size=(B, S_rows)).astype(np.int32),
        "res.service_id": rng.integers(0, 4, size=(B, R)).astype(np.int32),
    }
    n_spans = np.asarray([64, 50, 64, 3], dtype=np.int32)

    conds = (
        Cond(target=T_SPAN, col="span.dur_us", op="ge"),
        Cond(target=T_RES, col="res.service_id", op="eq"),
    )
    tree = ("and", ("cond", 0), ("cond", 1))
    operands = Operands.build([(0, 500, 0, 0.0, 0.0), (0, 2, 0, 0.0, 0.0)])

    tm, sc = sharded_search(mesh, tree, conds, operands, cols, n_spans, nt=NT)

    for b in range(B):
        valid = np.arange(S_rows) < n_spans[b]
        m1 = cols["span.dur_us"][b] >= 500
        m2 = cols["res.service_id"][b][cols["span.res_idx"][b]] == 2
        sm = m1 & m2 & valid
        counts = np.bincount(cols["span.trace_sid"][b][sm], minlength=NT)[:NT]
        np.testing.assert_array_equal(sc[b], counts)
        np.testing.assert_array_equal(tm[b], counts > 0)


def test_sharded_search_trace_cond_and_table(mesh):
    """Trace-axis conds inside the tree + dictionary-table (regex-style)
    predicates work on the sharded path."""
    rng = np.random.default_rng(9)
    from tempo_tpu.ops.filter import T_TRACE

    B, S_rows, NT = 2, 32, 8
    cols = {
        "span.trace_sid": rng.integers(0, NT, size=(B, S_rows)).astype(np.int32),
        "span.name_id": rng.integers(0, 6, size=(B, S_rows)).astype(np.int32),
        "trace.dur_us": rng.integers(0, 100, size=(B, NT)).astype(np.int32),
    }
    n_spans = np.asarray([32, 20], dtype=np.int32)
    conds = (
        Cond(target=T_SPAN, col="span.name_id", op="intable"),
        Cond(target=T_TRACE, col="trace.dur_us", op="ge"),
    )
    tree = ("and", ("cond", 0), ("cond", 1))
    table = np.asarray([0, 1, 0, 1, 0, 0], dtype=np.uint8)  # codes 1,3 match
    operands = Operands.build(
        [(0, 0, 0, 0.0, 0.0), (0, 40, 0, 0.0, 0.0)], tables={0: table}
    )
    tm, sc = sharded_search(mesh, tree, conds, operands, cols, n_spans, nt=NT)
    for b in range(B):
        valid = np.arange(S_rows) < n_spans[b]
        sm = np.isin(cols["span.name_id"][b], [1, 3]) & valid
        counts = np.bincount(cols["span.trace_sid"][b][sm], minlength=NT)[:NT]
        expected_tm = (counts > 0) & (cols["trace.dur_us"][b] >= 40)
        np.testing.assert_array_equal(tm[b], expected_tm)
        np.testing.assert_array_equal(sc[b], np.where(expected_tm, counts, 0))


def test_sharded_search_generic_attr_matches_oracle(mesh):
    """Generic sattr/rattr conds ({span.foo = "bar"} over the attr
    tables) run on the mesh: attr rows shard over sp, owner aggregation
    stitches across shard cuts with psum_scatter/psum. Checked against
    the numpy oracle on raggedy per-span attr counts that straddle the
    4-way sp split."""
    rng = np.random.default_rng(5)
    from tempo_tpu.ops.device import PAD_I32
    from tempo_tpu.ops.filter import T_RATTR, T_SATTR

    B, S_rows, NT, R = 2, 32, 8, 4
    A, RA = 64, 16  # sattr / rattr row buckets (multiples of sp=4)
    n_spans = np.asarray([32, 21], dtype=np.int32)

    cols = {
        "span.trace_sid": rng.integers(0, NT, size=(B, S_rows)).astype(np.int32),
        "span.res_idx": rng.integers(0, R, size=(B, S_rows)).astype(np.int32),
        "sattr.key_id": np.full((B, A), PAD_I32, np.int32),
        "sattr.vtype": np.full((B, A), PAD_I32, np.int32),
        "sattr.str_id": np.full((B, A), PAD_I32, np.int32),
        "sattr.off": np.zeros((B, S_rows + 1), np.int32),
        "rattr.key_id": np.full((B, RA), PAD_I32, np.int32),
        "rattr.vtype": np.full((B, RA), PAD_I32, np.int32),
        "rattr.int32": np.full((B, RA), PAD_I32, np.int32),
        "rattr.off": np.zeros((B, R + 1), np.int32),
    }
    sattr_real = []  # (key, vtype, str_id, owner) per block for the oracle
    rattr_real = []
    for b in range(B):
        counts = rng.integers(0, 4, size=n_spans[b])
        # truncate the tail so the rows fit in A while keeping raggedness
        over = np.cumsum(counts) > A
        counts[over] = 0
        assert counts.sum() > 0
        off = np.zeros(S_rows + 1, np.int32)
        off[1 : n_spans[b] + 1] = np.cumsum(counts)
        off[n_spans[b] + 1 :] = off[n_spans[b]]
        cols["sattr.off"][b] = off
        n_rows = int(off[-1])
        keys = rng.integers(0, 5, size=n_rows).astype(np.int32)
        vts = rng.integers(0, 2, size=n_rows).astype(np.int32)  # str/int mix
        vals = rng.integers(0, 6, size=n_rows).astype(np.int32)
        cols["sattr.key_id"][b, :n_rows] = keys
        cols["sattr.vtype"][b, :n_rows] = vts
        cols["sattr.str_id"][b, :n_rows] = vals
        owners = np.repeat(np.arange(n_spans[b]), counts)
        sattr_real.append((keys, vts, vals, owners))

        rcounts = rng.integers(0, 4, size=R)
        rcounts[np.cumsum(rcounts) > RA] = 0
        roff = np.concatenate([[0], np.cumsum(rcounts)]).astype(np.int32)
        cols["rattr.off"][b] = roff
        rn = int(roff[-1])
        rkeys = rng.integers(0, 3, size=rn).astype(np.int32)
        rvts = np.ones(rn, np.int32)  # int-typed
        rvals = rng.integers(0, 50, size=rn).astype(np.int32)
        cols["rattr.key_id"][b, :rn] = rkeys
        cols["rattr.vtype"][b, :rn] = rvts
        cols["rattr.int32"][b, :rn] = rvals
        rowners = np.repeat(np.arange(R), rcounts)
        rattr_real.append((rkeys, rvts, rvals, rowners))

    conds = (
        Cond(target=T_SATTR, col="str", op="eq"),      # span.foo = code 3
        Cond(target=T_RATTR, col="int", op="ge"),      # resource.bar >= 20
        Cond(target=T_SATTR, col="any", op="exists"),  # span.baz != nil
    )
    tree = ("and", ("cond", 0), ("or", ("cond", 1), ("cond", 2)))
    operands = Operands.build(
        [(2, 3, 0, 0.0, 0.0), (1, 20, 0, 0.0, 0.0), (4, 0, 0, 0.0, 0.0)]
    )
    tm, sc = sharded_search(mesh, tree, conds, operands, cols, n_spans, nt=NT)

    for b in range(B):
        keys, vts, vals, owners = sattr_real[b]
        rkeys, rvts, rvals, rowners = rattr_real[b]
        ns = n_spans[b]
        m0 = np.zeros(S_rows, bool)
        hit0 = (keys == 2) & (vts == 0) & (vals == 3)
        np.logical_or.at(m0, owners[hit0], True)
        rmask = np.zeros(R, bool)
        rhit = (rkeys == 1) & (rvts == 1) & (rvals >= 20)
        np.logical_or.at(rmask, rowners[rhit], True)
        m1 = rmask[cols["span.res_idx"][b]]
        m2 = np.zeros(S_rows, bool)
        np.logical_or.at(m2, owners[keys == 4], True)
        valid = np.arange(S_rows) < ns
        sm = m0 & (m1 | m2) & valid
        counts = np.bincount(cols["span.trace_sid"][b][sm], minlength=NT)[:NT]
        np.testing.assert_array_equal(sc[b], counts, err_msg=f"block {b}")
        np.testing.assert_array_equal(tm[b], counts > 0, err_msg=f"block {b}")


def test_sharded_search_struct_orphans_on_shard_cuts(mesh):
    """The '~' sibling relation's orphan rule (pid == -2 rows are
    mutual siblings when ANY lhs orphan exists) must survive the
    hoisted-gather refactor when orphans land on NON-ZERO sp shards --
    prior oracle coverage only ever placed orphans on shard 0. Checked
    against numpy for all three relations on rows whose parent chains
    and orphans straddle every one of the 4 shard cuts."""
    rng = np.random.default_rng(31)
    B, S_rows, NT = 2, 64, 8  # 4-way sp split: shards of 16 rows
    cols = {
        "span.trace_sid": np.sort(
            rng.integers(0, NT, size=(B, S_rows)).astype(np.int32), axis=1),
        "span.dur_us": rng.integers(0, 100, size=(B, S_rows)).astype(np.int32),
        "span.parent_idx": np.full((B, S_rows), -1, np.int32),
    }
    for b in range(B):
        sid = cols["span.trace_sid"][b]
        prev_same = np.zeros(S_rows, bool)
        prev_same[1:] = sid[1:] == sid[:-1]
        pidx = np.where(prev_same & (rng.random(S_rows) < 0.6),
                        np.arange(S_rows) - 1, -1).astype(np.int32)
        # orphans pinned onto shards 1..3 (rows 16+), never shard 0
        for row in (17, 33, 49, 62):
            pidx[row] = -2
        cols["span.parent_idx"][b] = pidx
    n_spans = np.asarray([64, 52], dtype=np.int32)  # ragged: pads shard 3
    conds = (
        Cond(target=T_SPAN, col="span.dur_us", op="lt"),
        Cond(target=T_SPAN, col="span.dur_us", op="ge"),
    )
    operands = Operands.build([(0, 80, 0, 0.0, 0.0), (0, 20, 0, 0.0, 0.0)])
    for op in (">", ">>", "~"):
        tree = ("struct", op, ("cond", 0), ("cond", 1))
        tm, sc = sharded_search(mesh, tree, conds, operands, cols, n_spans,
                                nt=NT)
        for b in range(B):
            valid = np.arange(S_rows) < n_spans[b]
            lhs = (cols["span.dur_us"][b] < 80) & valid
            rhs = (cols["span.dur_us"][b] >= 20) & valid
            pidx = cols["span.parent_idx"][b]
            has_p = (pidx >= 0) & valid
            safe = np.clip(pidx, 0, S_rows - 1)
            if op == ">":
                rel = has_p & lhs[safe]
            elif op == ">>":
                rel = np.zeros(S_rows, bool)
                for i in range(S_rows):
                    p = pidx[i] if valid[i] else -1
                    while p >= 0:
                        if lhs[p]:
                            rel[i] = True
                            break
                        p = pidx[p]
            else:  # '~'
                cnt = np.zeros(S_rows, np.int32)
                np.add.at(cnt, safe, (lhs & has_p).astype(np.int32))
                sibs = cnt[safe] - (lhs & has_p).astype(np.int32)
                orphan = (pidx == -2) & valid
                rel = (has_p & (sibs > 0)) | (orphan & np.any(lhs & orphan))
            sm = rhs & rel & valid
            counts = np.bincount(cols["span.trace_sid"][b][sm],
                                 minlength=NT)[:NT]
            np.testing.assert_array_equal(sc[b], counts,
                                          err_msg=f"{op} block {b}")
            np.testing.assert_array_equal(tm[b], counts > 0,
                                          err_msg=f"{op} block {b}")


def test_sharded_bloom_union(mesh):
    blooms = []
    all_ids = []
    for k in range(5):
        bl = ShardedBloom(4)
        ids = [bytes([k, i]) + b"\x00" * 14 for i in range(20)]
        bl.add_many(ids)
        all_ids.extend(ids)
        blooms.append(bl)
    u = sharded_bloom_union(mesh, blooms)
    for tid in all_ids:
        assert u.test(tid)
    # oracle: numpy OR
    expected = np.zeros_like(blooms[0].words)
    for b in blooms:
        expected |= b.words
    np.testing.assert_array_equal(u.words, expected)


def test_distributed_query_step_one_jit(mesh):
    """The composed step compiles and runs as a single jitted program."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    B, T, Q, S_rows, NT, R = 8, 32, 8, 32, 8, 4
    K, NS, W = 8, 2, 16

    ids = np.sort(rng.integers(0, 100, size=(B, T, 4)).astype(np.int32), axis=1)
    for b in range(B):
        ids[b] = ids[b][np.lexsort(ids[b].T[::-1])]
    n_valid = np.full((B,), T, dtype=np.int32)
    queries = ids[:, 0, :][:Q].copy()

    cols = {
        "span.trace_sid": rng.integers(0, NT, size=(B, S_rows)).astype(np.int32),
        "span.dur_us": rng.integers(0, 100, size=(B, S_rows)).astype(np.int32),
    }
    n_spans = np.full((B,), S_rows, dtype=np.int32)
    conds = (Cond(target=T_SPAN, col="span.dur_us", op="ge"),)
    tree = ("cond", 0)
    operands = Operands.build([(0, 50, 0, 0.0, 0.0)])
    blooms = rng.integers(0, 2**32, size=(K, NS, W), dtype=np.uint32)

    names = tuple(sorted(cols))
    step = distributed_query_step(mesh, tree, conds, names, B, T, Q, S_rows, R, NT, K, NS, W)
    hits, tm, sc, bu = step(
        jnp.asarray(ids), jnp.asarray(n_valid), jnp.asarray(queries),
        jnp.asarray(operands.ints), jnp.asarray(operands.floats),
        jnp.asarray(n_spans),
        tuple(jnp.asarray(cols[n]) for n in names),
        jnp.asarray(blooms),
    )
    assert hits.shape == (Q, 2)
    assert np.asarray(tm).shape == (B, NT)
    expected_union = np.zeros((NS, W), dtype=np.uint32)
    for k in range(K):
        expected_union |= blooms[k]
    np.testing.assert_array_equal(np.asarray(bu), expected_union)


def test_graft_dryrun_multichip_entry():
    """Run the toy correctness leg the driver invokes first
    (__graft_entry__.dryrun_multichip's fast-failure shape) on the
    virtual 8-device CPU mesh, so a driver-side failure reproduces
    here. The default toy-then-scale run is covered (once) by
    test_graft_dryrun_scale_shape."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    try:
        import __graft_entry__ as graft

        graft.dryrun_multichip(8, scale=False)
    finally:
        sys.path.pop(0)


def test_graft_dryrun_scale_shape(capsys):
    """The default (toy-then-scale) dryrun: >= 1M padded span rows per
    chip, ragged per-block sizes, generic-attr conds, a struct-op node,
    the batched (Q>1) multi-query mesh window, the per-chip memory
    budget INCLUDING the batched program's padded Q-axis, and the host
    oracle -- the dryrun stand-in for the 100M-span sharded Find/search
    baseline config. The MULTICHIP artifact tail (scale shape + comm
    walker volume) must be printed and well-formed."""
    import json
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    try:
        import __graft_entry__ as graft

        graft.dryrun_multichip(8, scale=True)
    finally:
        sys.path.pop(0)
    tail_lines = [ln for ln in capsys.readouterr().out.splitlines()
                  if ln.startswith("MULTICHIP_SCALE ")]
    assert tail_lines, "scale dryrun printed no artifact tail"
    tail = json.loads(tail_lines[-1].split(" ", 1)[1])
    assert tail["padded_rows_per_chip"] >= 1_000_000
    assert tail["mq_window_q"] > 1 and tail["struct_op"]
    assert tail["per_chip_bytes"] <= tail["budget_bytes"]
    assert "mesh_step" in tail["comm_bytes_per_launch"]
    assert "mesh_multiquery" in tail["comm_bytes_per_launch"]


def test_graft_dryrun_subprocess_fallback(monkeypatch):
    """When the CPU backend (asked for explicitly) has fewer devices
    than the dryrun wants, it re-runs in a fresh subprocess with that
    many virtual CPU devices."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    try:
        import __graft_entry__ as graft

        monkeypatch.setattr(graft, "_force_virtual_devices", lambda n: False)
        graft.dryrun_multichip(8, scale=False)  # --no-scale flag plumbing
    finally:
        sys.path.pop(0)


def test_graft_dryrun_refuses_to_leave_the_chips(monkeypatch):
    """Asking an accelerator backend for more devices than it has is an
    error, and the CPU route is taken only when the CPU was asked for:
    the dryrun never quietly swaps real chips for virtual devices."""
    import sys
    from pathlib import Path

    import jax
    import pytest

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    try:
        import __graft_entry__ as graft
    finally:
        sys.path.pop(0)
    assert graft._force_virtual_devices(len(jax.devices())) is True
    assert graft._force_virtual_devices(len(jax.devices()) + 1) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="tpu backend has"):
        graft._force_virtual_devices(len(jax.devices()) + 1)


def test_graft_entry_compiles():
    import sys
    from pathlib import Path

    import jax

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    try:
        import __graft_entry__ as graft

        fn, args = graft.entry()
        sids, mask, counts = jax.jit(fn)(*args)
        assert sids.shape[0] == args[1].shape[0]
    finally:
        sys.path.pop(0)
