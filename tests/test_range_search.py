"""A search is a time range over a blocklist: the served multi-block path
(Frontend -> block-batch jobs -> search_blocks_fused, and metrics time
shards -> metrics_exec) against the per-block host twins combined as the
semantics say -- set union for a search, bucket-wise sum for rate(), any
`limit` distinct members where more match. CPU, twelve small seeded blocks
dated an hour apart like the benchmark's hourly blocklist. The database is
given a one-device mesh: conftest's eight virtual devices would send a block
set to the stacked mesh program, and this is the one-chip deployment's path.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from tempo_tpu.block import open_block
from tempo_tpu.db import metrics_exec as mx
from tempo_tpu.db import route as route_mod
from tempo_tpu.db.search import SearchRequest, search_block, search_blocks_fused
from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
from tempo_tpu.parallel import make_mesh
from tempo_tpu.services.frontend import FIND_SHARD_BLOCKS, Frontend
from tempo_tpu.services.querier import Querier
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.testdata import make_traces, synth_block

TENANT = "t"
N_BLOCKS = 12
HOUR_S, GAP_S = 3600, 180
TOP_S = 1_700_000_000 - 1_700_000_000 % HOUR_S
RANGES = (1, 3, N_BLOCKS)

QUERIES = {
    # a span that carries the key at all: ~2 % of the spans, every block
    "attr": dict(query='{ span.attr.key007 != "nope" }', limit=5000),
    "tag": dict(tags={"service.name": "svc-003"}, limit=5000),
    "duration": dict(query="{ duration > 900ms }", limit=5000),
}


def _base_ns(b: int) -> int:
    return (TOP_S - (b + 1) * (HOUR_S + GAP_S)) * 1_000_000_000


@pytest.fixture(scope="module")
def hourly(tmp_path_factory):
    """-> (db, metas newest first): N_BLOCKS seeded blocks, block b filling
    the hour that starts (b + 1) x 3,780 s before TOP_S, and one older
    hand-made block whose traces alone carry `span.only = "last"`."""
    root = tmp_path_factory.mktemp("hourly")
    db = TempoDB(TempoDBConfig(
        backend={"backend": "local", "path": str(root / "store")},
        wal_path=str(root / "wal"), device_promote_touches=1))
    db._mesh = make_mesh(n_devices=1)  # one chip: the fused engine, not the mesh program
    metas = []
    for b in range(N_BLOCKS):
        meta, _ = synth_block(db.backend, TENANT, np.random.default_rng([34, b]),
                              300, 6, n_res=64, base_time_ns=_base_ns(b))
        metas.append(meta)
    last = make_traces(12, seed=34, n_spans=4, base_time_ns=_base_ns(N_BLOCKS))
    for _, tr in last:
        for _, _, sp in tr.all_spans():
            sp.attrs["only"] = "last"
    metas.append(db.write_block(TENANT, last))
    db.poll_now()
    yield db, metas
    db.close()


@pytest.fixture
def frontend(hourly, monkeypatch):
    """A frontend whose block-batch jobs hold about three blocks, on a
    link so slow to beat that no host scan is estimated cheaper."""
    db, metas = hourly
    monkeypatch.setattr(route_mod, "link_rtt_ms", lambda: -1.0)
    size = max(m.size_bytes or 1 for m in metas[:N_BLOCKS])
    fe = Frontend(Querier(db, None, lambda a: None), n_workers=2,
                  batch_bytes=3 * size + 1)
    fe.result_cache = None
    yield fe
    fe.stop()


def _window(metas, n: int) -> dict:
    """The newest n blocks: the n-th newest's first second to the newest's
    last, as the benchmark's range shapes ask."""
    return {"start": metas[n - 1].start_time_unix_nano // 10**9,
            "end": metas[0].end_time_unix_nano // 10**9 + 1}


def _twin_ids(db, metas, req: SearchRequest) -> set[str]:
    """The per-block host twin (ops/hostfilter through search_block), one
    block at a time, united."""
    out: set[str] = set()
    for m in metas:
        if m.overlaps_time(req.start, req.end):
            blk = open_block(db.backend, TENANT, m.block_id)
            out |= {t.trace_id for t in search_block(blk, req, mode="host").traces}
    return out


@pytest.mark.parametrize("n", RANGES)
@pytest.mark.parametrize("shape", sorted(QUERIES))
def test_range_search_equals_the_union_of_the_blocks_twins(hourly, frontend, shape, n):
    db, metas = hourly
    req = SearchRequest(**QUERIES[shape], **_window(metas, n))
    before = TEL.range_stats()
    got = frontend.search(TENANT, req)
    want = _twin_ids(db, metas, req)
    assert want, "the twin finds nothing: a bad operand"
    ids = [t.trace_id for t in got.traces]
    assert len(ids) == len(set(ids))
    assert set(ids) == want
    after = TEL.range_stats()
    assert after["searches"] == before["searches"] + 1
    assert after["by_blocks"].get(str(n), 0) == before["by_blocks"].get(str(n), 0) + 1
    assert after["jobs"] - before["jobs"] == -(-n // 3)  # three blocks a job
    assert after["job_blocks"] - before["job_blocks"] == n


def test_a_range_makes_fused_groups_and_counts_them(hourly, frontend):
    db, metas = hourly
    rows0 = {k: v for k, v in TEL.routing_counts().items() if k[0] == "search_fused"}
    st0 = TEL.stage_stats()
    frontend.search(TENANT, SearchRequest(**QUERIES["duration"], **_window(metas, 6)))
    rows = {k: v - rows0.get(k, 0) for k, v in TEL.routing_counts().items()
            if k[0] == "search_fused"}
    assert sum(rows.values()) == 6  # one decision a block
    st = TEL.stage_stats()

    def delta(name):
        return st.get(name, {}).get("count", 0) - st0.get(name, {}).get("count", 0)

    assert delta("search:fused") == 2  # two jobs of three blocks
    assert delta("search:merge") >= 2  # a merge a job, and the last cut
    assert delta("topk:collect") >= 2


@pytest.mark.parametrize("where", ["newest_job", "last_job"])
def test_limit_below_the_matches_gives_exactly_limit_members(hourly, frontend, where):
    db, metas = hourly
    if where == "newest_job":
        req = SearchRequest(query="{ duration > 100ms }", limit=7,
                            **_window(metas, N_BLOCKS))
    else:  # every match sits in the oldest block: the last job built
        req = SearchRequest(query='{ span.only = "last" }', limit=7,
                            **_window(metas, N_BLOCKS + 1))
    want = _twin_ids(db, metas, SearchRequest(
        query=req.query, limit=100_000, start=req.start, end=req.end))
    assert len(want) > 7
    got = [t.trace_id for t in frontend.search(TENANT, req).traces]
    assert len(got) == 7 and len(set(got)) == 7
    assert set(got) <= want


def _fresh(db, metas, touches):
    """Readers of their own (no state from another test), `touches[i]`
    searches behind them: 0 = cold (host engine), 1 = worth staging."""
    blocks = []
    for m, t in zip(metas, touches):
        blk = open_block(db.backend, TENANT, m.block_id)
        blk.promote_touches = 2
        blk.search_touches = t
        blocks.append(blk)
    return blocks


@pytest.mark.parametrize("shape", sorted(QUERIES))
@pytest.mark.parametrize("split", ["device_only", "host_only", "split"])
def test_a_groups_engine_split_does_not_change_the_answer(hourly, monkeypatch, split, shape):
    db, metas = hourly
    monkeypatch.setattr(route_mod, "link_rtt_ms", lambda: -1.0)
    group = metas[:5]
    touches = {"device_only": [1] * 5, "host_only": [0] * 5,
               "split": [1, 0, 1, 0, 1]}[split]
    req = SearchRequest(**QUERIES[shape], **_window(metas, 5))
    rows0 = dict(TEL.routing_counts())
    got = search_blocks_fused(_fresh(db, group, touches), req)
    rows = {k[1]: v - rows0.get(k, 0) for k, v in TEL.routing_counts().items()
            if k[0] == "search_fused" and v - rows0.get(k, 0)}
    assert rows == {e: n for e, n in (("device", sum(touches)),
                                      ("host", 5 - sum(touches))) if n}
    assert {t.trace_id for t in got.traces} == _twin_ids(db, group, req)


# ------------------------------------------------------------------ rate()
RATE_Q = '{ resource.service.name = "svc-003" } | rate()'


def _rate_counts(resp) -> np.ndarray:
    assert len(resp.series) == 1
    return np.asarray(next(iter(resp.series.values()))["count"])


def _column_counts(db, metas, req) -> np.ndarray:
    """rate()'s buckets from the blocks' own columns: nothing of
    metrics_exec or an engine in it."""
    out = np.zeros(req.n_buckets, np.int64)
    for m in metas:
        blk = open_block(db.backend, TENANT, m.block_id)
        if "span.res_idx" not in blk.pack.names():
            continue
        svc = blk.dictionary.lookup("svc-003")
        res_svc = blk.pack.read("res.service_id")
        hit = res_svc[blk.pack.read("span.res_idx")] == svc
        start_ms = (blk.pack.read("span.start_ms").astype(np.int64)
                    + m.start_time_unix_nano // 1_000_000)
        b = (start_ms[hit] - req.start_ms) // req.step_ms
        b = b[(b >= 0) & (b < req.n_buckets)]
        out += np.bincount(b, minlength=req.n_buckets)
    return out


@pytest.mark.parametrize("step_s", [60, 420, 1000])
@pytest.mark.parametrize("n", [1, 3, 6])
def test_rate_sums_bucket_by_bucket_across_blocks(hourly, frontend, n, step_s):
    """60 s: many time shards; 420 s: buckets straddle the 179 s gap between
    neighbours (3,780 = 9 x 420); 1,000 s: a step that does not divide the
    range."""
    db, metas = hourly
    w = _window(metas, n)
    req = mx.align_params(RATE_Q, w["start"] - 7, w["end"], step_s)
    got = _rate_counts(frontend.metrics_query_range(TENANT, req))
    blocks = [open_block(db.backend, TENANT, m.block_id) for m in metas[:N_BLOCKS]]
    twin = _rate_counts(mx.metrics_query_range_blocks(blocks, req, mode="host"))
    assert twin.sum() > 0
    assert got.tolist() == twin.tolist()
    assert got.tolist() == _column_counts(db, metas[:N_BLOCKS], req).tolist()


# ------------------------------------------------------------------- finds
@pytest.fixture(scope="module")
def blooms(tmp_path_factory):
    """32 tiny blocks: a find's candidates split into two shard jobs."""
    root = tmp_path_factory.mktemp("blooms")
    db = TempoDB(TempoDBConfig(
        backend={"backend": "local", "path": str(root / "store")},
        wal_path=str(root / "wal")))
    db._mesh = make_mesh(n_devices=1)
    ids = []
    for b in range(2 * FIND_SHARD_BLOCKS):
        _, block_ids = synth_block(db.backend, TENANT, np.random.default_rng([35, b]),
                                   40, 3, n_res=8, base_time_ns=_base_ns(b))
        ids.append(block_ids)
    db.poll_now()
    fe = Frontend(Querier(db, None, lambda a: None), n_workers=2)
    fe.result_cache = None
    yield fe, ids
    fe.stop()
    db.close()


def test_a_find_reaches_the_oldest_block(blooms):
    fe, ids = blooms
    jobs0 = fe.stats_jobs_local
    tid = ids[-1][17].tobytes()
    tr = fe.find_trace_by_id(TENANT, tid)
    assert tr is not None
    assert sum(len(ss.spans) for rs in tr.resource_spans for ss in rs.scope_spans) == 3
    assert fe.stats_jobs_local - jobs0 >= 2  # two shard jobs of 16 blooms


def test_a_miss_over_32_blooms_is_none(blooms):
    fe, _ = blooms
    assert fe.find_trace_by_id(TENANT, random.Random(34).randbytes(16)) is None


# ------------------------------------------------- a closed set of programs
LADDER = (1, 3, 6, 12)  # the time picker's presets this corpus can hold


def _send(fe, metas, shape: str, n: int, rnd: random.Random) -> None:
    """One request of `shape` over the newest n blocks, operands from rnd."""
    w = _window(metas, n)
    svc = f"svc-{rnd.randrange(64):03d}"
    if shape == "rate":
        fe.metrics_query_range(TENANT, mx.align_params(
            f'{{ resource.service.name = "{svc}" }} | rate()',
            w["start"] - rnd.randrange(170), w["end"], 60))
        return
    req = {"attr": dict(query=f'{{ span.attr.key{rnd.randrange(1, 100):03d} != "nope" }}',
                        limit=5000),
           "tag": dict(tags={"service.name": svc}, limit=300 * n + 100),
           "duration": dict(query=f"{{ duration > {rnd.randrange(900_000, 990_000)}us }}",
                            limit=20)}[shape]
    fe.search(TENANT, SearchRequest(**req, start=w["start"] - rnd.randrange(170),
                                    end=w["end"]))


def _programs() -> tuple[set, int]:
    snap = TEL.snapshot()
    return ({(k["op"], k["bucket"]) for k in snap["kernels"]},
            snap["jit_cache"]["compiles_total"])


@pytest.mark.parametrize("seed", [1, 2])
def test_a_window_launches_no_program_the_warm_up_did_not(hourly, frontend,
                                                         monkeypatch, seed):
    """Warm-up sends every (shape, N) pair once; a window then draws shapes,
    ranges and operands from its seed, and the router's per-block verdicts
    are flipped at random beside it (what the host array cache and the
    staged cache do to route_fused per query). Neither may reach a compiled
    shape that warm-up did not: the (op, bucket) launch keys and the count
    of compile signatures stay as they were."""
    db, metas = hourly
    rnd = random.Random(seed)
    for shape in ("attr", "tag", "duration", "rate"):
        for n in LADDER:
            _send(frontend, metas, shape, n, rnd)
    warm = _programs()

    decide = route_mod.route_fused

    def flipped(live):
        routes = decide(live)
        if routes is None:
            return None
        return [route_mod.Route("host", "host_scan_cheaper")
                if r.engine == "device" and rnd.random() < 0.4 else r
                for r in routes]

    monkeypatch.setattr(route_mod, "route_fused", flipped)
    for _ in range(60):
        _send(frontend, metas, rnd.choice(("attr", "tag", "duration", "rate")),
              rnd.choices(LADDER, weights=(35, 25, 20, 20))[0], rnd)
    assert _programs() == warm


@pytest.mark.parametrize("lens,slots,k", [
    ((512,), 1, 64), ((512, 512, 512), 3, 64), ((512, 512), 4, 2048),
    ((512, 1024), 2, 64), ((256, 1024, 512), 5, 16384),
], ids=["one", "three_of_four", "two_of_four_slots", "widened", "widened_rung_8"])
def test_the_group_select_ranks_like_its_host_twin(lens, slots, k):
    """Whatever parts fill the group's slots, and whether their buckets
    differ: the winners, their counts and the match total are the numpy
    twin's, and the program is keyed by the group alone."""
    from tempo_tpu.ops.select import (
        group_rung, select_topk_device_multi, select_topk_host_multi)

    rng = np.random.default_rng(len(lens) * 1000 + slots)
    part_len = max(lens)
    masks = [rng.random(n) < 0.3 for n in lens]
    keys = [rng.permutation(100_000)[:n].astype(np.int32) for n in lens]
    cnts = [rng.integers(1, 9, n).astype(np.int32) for n in lens]
    gids, gc, n_match = select_topk_device_multi(masks, keys, cnts, k, slots, part_len)
    assert TEL.last_launch()[:2] == ("select", str(min(k, group_rung(slots) * part_len)))
    hids, hc, h_match = select_topk_host_multi(masks, keys, cnts, k)
    offs = np.cumsum([0] + list(lens))
    want = {}
    for h, c in zip(hids, hc):
        part = int(np.searchsorted(offs, h, side="right")) - 1
        want[(part, int(h - offs[part]))] = int(c)
    got = {(int(g) // part_len, int(g) % part_len): int(c) for g, c in zip(gids, gc)}
    assert n_match == h_match == sum(int(m.sum()) for m in masks)
    assert got == want
