"""Randomized three-way TraceQL equivalence: host engine vs device
engine vs the wire-model oracle (traceql.hosteval.trace_matches).

The hand-picked equivalence tests cover known-interesting queries; this
fuzzer composes queries from the grammar's building blocks over random
blocks and demands the two production engines and the oracle agree on
the EXACT trace set for every one. Deterministic seeds (no wall-clock
randomness); the generator is biased toward values that exist in the
testdata vocabulary so most queries have non-trivial match sets.
"""

import random

import pytest

from tempo_tpu.backend import MemBackend
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.db.search import SearchRequest, search_block
from tempo_tpu.traceql.hosteval import trace_matches
from tempo_tpu.traceql.parser import parse
from tempo_tpu.util.testdata import make_traces

TENANT = "t1"

_STR_FIELDS = [
    ("span.http.method", ["GET", "POST", "PUT", "nope"]),
    ("span.component", ["net/http", "grpc", "sql", "nope"]),
    ("resource.service.name", ["db", "auth", "frontend", "nope"]),
    (".service.name", ["db", "payments", "nope"]),
    ("name", ["GET /api", "db.query", "render", "nope"]),
]
_INT_FIELDS = [
    ("span.http.status_code", [200, 404, 500, 123]),
]
_DUR = ["1ms", "100ms", "1s", "1500ms"]
_KINDS = ["server", "client", "internal", "producer", "consumer"]


def _leaf(rng: random.Random) -> str:
    roll = rng.random()
    if roll < 0.35:
        f, vals = rng.choice(_STR_FIELDS)
        op = rng.choice(["=", "!=", "=~", "!~"])
        v = rng.choice(vals)
        if op in ("=~", "!~"):
            v = v[: max(1, len(v) // 2)]  # prefix-ish regex
            return f'{f} {op} "{v}.*"'
        return f'{f} {op} "{v}"'
    if roll < 0.5:
        f, vals = rng.choice(_INT_FIELDS)
        return f"{f} {rng.choice(['=', '!=', '<', '<=', '>', '>='])} {rng.choice(vals)}"
    if roll < 0.65:
        return f"duration {rng.choice(['>', '>=', '<', '<='])} {rng.choice(_DUR)}"
    if roll < 0.75:
        return f"kind = {rng.choice(_KINDS)}"
    if roll < 0.85:
        return f"status {rng.choice(['=', '!='])} error"
    if roll < 0.93:
        return f"traceDuration {rng.choice(['>', '<'])} {rng.choice(_DUR)}"
    return f'span.cache.hit = {rng.choice(["true", "false"])}'


def _expr(rng: random.Random, depth: int = 0) -> str:
    if depth >= 2 or rng.random() < 0.45:
        return _leaf(rng)
    op = rng.choice(["&&", "||"])
    lhs, rhs = _expr(rng, depth + 1), _expr(rng, depth + 1)
    return f"({lhs} {op} {rhs})" if rng.random() < 0.5 else f"{lhs} {op} {rhs}"


def _query(rng: random.Random) -> str:
    q = f"{{ {_expr(rng)} }}"
    roll = rng.random()
    if roll < 0.12:
        q = f"{q} {rng.choice(['>', '>>', '~'])} {{ {_leaf(rng)} }}"
    elif roll < 0.3:
        # spanset combinators: each leaf keeps its OWN same-span group,
        # and mixed span/trace ORs inside a leaf must keep verification
        # (a fuzz-found planner regression lost exactly that flag)
        q = f"{q} {rng.choice(['&&', '||'])} {{ {_expr(rng)} }}"
    elif roll < 0.38:
        agg = rng.choice([
            f"count() {rng.choice(['>', '>=', '<', '='])} {rng.choice([0, 1, 2, 5])}",
            f"avg(duration) {rng.choice(['>', '<'])} {rng.choice(_DUR)}",
            f"max(span.http.status_code) {rng.choice(['>=', '<'])} 500",
        ])
        q = f"{q} | {agg}"
    return q


# Tier-1 runs a deterministic PREFIX of each seed's query stream (the
# first _QUICK cases); the full-depth streams ride in tier-2 under the
# slow marker. Same seeds, same generator state, so a quick-run failure
# always reproduces at full depth -- the split only moves wall-clock
# (device-engine compiles dominate at ~7s/query) out of the 870s tier-1
# budget.
_QUICK = 12
_FULL = 40


def _depths(seeds):
    for s in seeds:
        yield pytest.param(s, _QUICK, id=f"{s}")
        yield pytest.param(s, _FULL, id=f"{s}-full", marks=pytest.mark.slow)


@pytest.mark.parametrize("seed,n_cases", _depths([101, 202, 303]))
def test_fuzz_host_device_oracle_agree(tmp_path, seed, n_cases):
    rng = random.Random(seed)
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")), backend=MemBackend())
    traces = make_traces(50, seed=seed, n_spans=8)
    db.write_block(TENANT, traces)
    blk = db.open_block(db.blocklist.metas(TENANT)[0])

    checked = 0
    for _ in range(n_cases):
        q = _query(rng)
        ast = parse(q)  # generator only emits grammar-valid queries
        want = {tid.hex() for tid, t in traces if trace_matches(ast, t)}
        got_h = {t.trace_id for t in search_block(
            blk, SearchRequest(query=q, limit=1000), mode="host").traces}
        assert got_h == want, (q, sorted(got_h ^ want)[:4])
        got_d = {t.trace_id for t in search_block(
            blk, SearchRequest(query=q, limit=1000), mode="device").traces}
        assert got_d == want, (q, sorted(got_d ^ want)[:4])
        checked += 1
    assert checked == n_cases


@pytest.mark.parametrize("seed,n_cases", _depths([606, 707]))
def test_fuzz_windowed_host_device_oracle_agree(tmp_path, seed, n_cases):
    """The same three-way agreement under a start/end window that cuts the
    block: traces spread over six seconds, many within a millisecond of
    the window's edges (the device compares milliseconds widened by one;
    db/search._candidates settles the nanoseconds). Plans whose query
    conditions are exact no longer host-verify under a window, plans with
    a lossy condition still do, and both must return the oracle's set."""
    from tempo_tpu.db.search import _plan_for_block
    from tempo_tpu.util.testdata import restart_trace

    ns, base_s = 10**9, 1_700_000_000
    start, end = base_s + 2, base_s + 4
    rng = random.Random(seed)
    traces = []
    for tid, t in make_traces(50, seed=seed, n_spans=8):
        edge = rng.choice([start, end]) * ns
        at = rng.choice([
            base_s * ns + rng.randrange(6 * ns),
            edge + rng.choice([-1_000_000, -999_999, -1, 0, 1, 999_999, 1_000_001]),
        ])
        traces.append((tid, restart_trace(t, at)))
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")), backend=MemBackend())
    db.write_block(TENANT, traces)
    blk = db.open_block(db.blocklist.metas(TENANT)[0])
    inside = {tid.hex() for tid, t in traces
              if start * ns <= t.time_range_nanos()[0] <= end * ns}
    assert 5 < len(inside) < 45

    verified = 0
    for _ in range(n_cases):
        q = _query(rng)
        ast = parse(q)
        want = {tid.hex() for tid, t in traces if trace_matches(ast, t)} & inside
        req = SearchRequest(query=q, limit=1000, start=start, end=end)
        p = _plan_for_block(blk, req)
        verified += bool(not p.prune and p.needs_verify)
        for mode in ("host", "device"):
            got = {t.trace_id for t in search_block(blk, req, mode=mode).traces}
            assert got == want, (q, mode, sorted(got ^ want)[:4])
    assert 0 < verified < n_cases, f"{verified} of {n_cases} plans verify"


@pytest.mark.parametrize("seed,n_cases", _depths([404, 505]))
def test_fuzz_mesh_path_agrees(tmp_path, seed, n_cases):
    """Fourth leg: the stacked MESH program (blocks over dp, span AND
    generic-attr rows over sp, structural ops via all_gathered parent
    tables, parallel/search.py) against the wire oracle on the
    8-virtual-device mesh."""
    from tempo_tpu.db.search import search_blocks_device

    rng = random.Random(seed)
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")), backend=MemBackend())
    traces1 = make_traces(30, seed=seed, n_spans=6)
    traces2 = make_traces(30, seed=seed + 1, n_spans=6)
    db.write_block(TENANT, traces1)
    # second block written DOWN-LEVEL (vtpu1, JSON footer): the mesh
    # program must stack mixed-version blocks transparently
    from tempo_tpu.block.builder import BlockBuilder, write_block

    b = BlockBuilder(TENANT)
    for tid, t in sorted(traces2):
        b.add_trace(tid, t)
    m1 = write_block(db.backend, b.finalize(), version="vtpu1")
    db.blocklist.update(TENANT, add=[m1])
    blocks = [db.open_block(m) for m in db.blocklist.metas(TENANT)]
    assert {b.meta.version for b in blocks} == {"vtpu1", "vtpu2"}
    assert db.mesh.devices.size == 8
    all_traces = traces1 + traces2

    mesh_ran = 0
    for _ in range(n_cases):
        q = _query(rng)
        ast = parse(q)
        want = {tid.hex() for tid, t in all_traces if trace_matches(ast, t)}
        resp = search_blocks_device(blocks, SearchRequest(query=q, limit=1000), db.mesh)
        if resp is None:
            continue
        got = {t.trace_id for t in resp.traces}
        assert got == want, (q, sorted(got ^ want)[:4])
        mesh_ran += 1
    assert mesh_ran >= n_cases // 2, f"only {mesh_ran} queries ran the mesh path"
