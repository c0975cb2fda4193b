"""What a configuration's `corpus` can say about its layout (PR 40): several
blocks in one compaction window, more than one tenant, workers in batches.
At the defaults nothing moves; the new layouts group as the program's own
`select_jobs` groups them. CPU, tiny sizes, no server."""

import json
import os
import threading
import time

import pytest

from benchmarks.lib import corpus, harness as H, rangeutil as R, shapeutil as U

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIGS = ["chip1-4block", "host4-4block", "host4-scalable-4block", "chip1-32hourly"]


def config(name: str) -> dict:
    return H.load_json(os.path.join(BENCH, "configs", name + ".json"))


def small(blocks: int, **layout) -> dict:
    """A configuration a test can build in a second or two."""
    return {"name": "t", "chips": 1, "blocks": blocks,
            "corpus": {"traces_per_block": 120, "spans_per_trace": 4,
                       "resources": 16, "attrs_per_span": 2, "gap_s": 180,
                       "max_age_h": 12, "keep_corpora": 3,
                       "block_popularity": [1.0], **layout},
            "tiny_corpus": {}}


@pytest.fixture
def in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(corpus, "bench_dir",
                        lambda *parts: os.path.join(str(tmp_path), *parts))
    return tmp_path


def build(cfg: dict, seed: int = 7) -> dict:
    return corpus.ensure(cfg, "tiny", seed, log=lambda m: None)


def env_of(cfg: dict, manifest: dict) -> H.Env:
    return H.Env(cfg, {"name": "t"}, manifest, 7)


def stored_metas(manifest: dict, tenant: str):
    from tempo_tpu.block.meta import BlockMeta

    root = os.path.join(manifest["path"], "store", tenant)
    out = []
    for block_id in sorted(os.listdir(root)):
        with open(os.path.join(root, block_id, "meta.json")) as f:
            out.append(BlockMeta.from_json(f.read()))
    return out


# ------------------------------------------------------------- the defaults
@pytest.mark.parametrize("scale", ["full", "tiny"])
@pytest.mark.parametrize("name", CONFIGS)
def test_defaults_keep_the_key_and_the_dating(name, scale):
    sz = corpus.sizes(config(name), scale)
    legacy = "b{blocks}-t{traces_per_block}x{spans_per_trace}-g{gap_s}".format(**sz)
    assert corpus.cache_key(sz) == legacy
    plan = corpus.layout(sz)
    assert plan == [{"tenant": "single-tenant", "window": b}
                    for b in range(sz["blocks"])]
    top = 1_800_000_000 * 10**9
    for b, at in enumerate(plan):
        assert corpus.window_base_ns(top, at["window"], sz["gap_s"]) == (
            top - (b + 1) * (corpus.HOUR_NS + sz["gap_s"] * 1_000_000_000))


def test_configurations_of_one_size_share_one_corpus():
    keys = {n: corpus.cache_key(corpus.sizes(config(n), "full")) for n in CONFIGS}
    assert keys["chip1-4block"] == keys["host4-4block"] == keys["host4-scalable-4block"]
    assert keys["chip1-32hourly"] != keys["chip1-4block"]


@pytest.mark.parametrize("layout,suffix", [
    ({}, ""),
    ({"blocks_per_window": 1}, ""),
    ({"tenants": [{"name": "single-tenant", "blocks": 8}]}, ""),
    ({"blocks_per_window": 1, "tenants": [{"name": "single-tenant", "blocks": 8}]}, ""),
    ({"blocks_per_window": 2}, "-w2"),
    ({"blocks_per_window": 4}, "-w4"),
])
def test_key_rule(layout, suffix):
    sz = corpus.sizes(small(8, **layout), "full")
    assert corpus.cache_key(sz) == "b8-t120x4-g180" + suffix


def test_key_names_the_tenants():
    two = [{"name": "a", "blocks": 4}, {"name": "b", "blocks": 4}]
    k2 = corpus.cache_key(corpus.sizes(small(8, tenants=two), "full"))
    assert k2.startswith("b8-t120x4-g180-n2x")
    other = [{"name": "a", "blocks": 5}, {"name": "b", "blocks": 3}]
    assert corpus.cache_key(corpus.sizes(small(8, tenants=other), "full")) != k2
    both = corpus.cache_key(corpus.sizes(
        small(8, tenants=two, blocks_per_window=2), "full"))
    assert both == k2.replace("-n2x", "-w2-n2x")


def test_a_layout_that_does_not_add_up_is_refused():
    with pytest.raises(ValueError):
        corpus.layout(corpus.sizes(small(8, tenants=[{"name": "a", "blocks": 3}]), "full"))
    with pytest.raises(ValueError):
        corpus.layout(corpus.sizes(small(8, blocks_per_window=0), "full"))


# ------------------------------------------------- blocks in one window
@pytest.mark.parametrize("k", [1, 2, 4])
def test_blocks_per_window_groups_as_select_jobs_groups(in_tmp, k):
    from tempo_tpu.db.compactor import CompactorConfig, select_jobs

    cfg = small(8, blocks_per_window=k)
    m = build(cfg)
    blocks = m["blocks"]
    assert [b["window"] for b in blocks] == [b // k for b in range(8)]
    assert [b["index"] for b in blocks] == list(range(8))
    now_s = time.time()
    by_window: dict = {}
    for b in blocks:
        assert b["end_s"] <= now_s  # whole in the past
        base_s = b["base_time_ns"] // 10**9  # and inside its window's hour
        assert base_s <= b["start_s"] and b["end_s"] <= base_s + 3600 + 2
        by_window.setdefault(b["window"], []).append(b)
    for w, mates in by_window.items():
        assert len({b["end_s"] // 3600 for b in mates}) == 1
        assert len({b["base_time_ns"] for b in mates}) == 1
        older = by_window.get(w + 1)
        if older:  # neighbouring windows: gap_s - 1 s apart, to the second
            gap = min(b["start_s"] for b in mates) - max(b["end_s"] for b in older)
            assert gap >= 180 - 2  # end_s is the last second + 1
            assert mates[0]["base_time_ns"] - older[0]["base_time_ns"] == (
                3600 + 180) * 10**9
    # disjoint trace ids: every block its own seed stream
    env = env_of(cfg, m)
    ids = [bytes(r) for b in range(8) for r in env.block_ids(b)]
    assert len(set(ids)) == len(ids) == 8 * 120
    jobs = select_jobs("single-tenant", stored_metas(m, "single-tenant"),
                       CompactorConfig())
    if k == 1:
        assert jobs == []  # as before: nothing to merge
    else:
        assert sorted(len(j.blocks) for j in jobs) == [k] * (8 // k)
        by_id = {b["block_id"]: b["window"] for b in blocks}
        for j in jobs:
            assert len({by_id[x.block_id] for x in j.blocks}) == 1


def test_a_blocks_window_covers_its_mates_whole(in_tmp):
    cfg = small(4, blocks_per_window=2)
    m = build(cfg)
    env = env_of(cfg, m)
    for b in range(4):
        win = U.window(env, b)
        mates = [x for x in m["blocks"] if x["window"] == b // 2]
        assert win["start"] == min(x["start_s"] for x in mates)
        assert win["end"] == max(x["end_s"] for x in mates)
        assert U.blocks_overlapping(env, win["start"], win["end"]) == [
            x["index"] for x in mates]
    # "the last N hours" counts windows, not blocks
    assert [[b["index"] for b in h] for h in R.hours(env)] == [[0, 1], [2, 3]]
    assert R.draw_n(env, {"blocks": [1, 3]}, "s") == 1
    assert R.draw_n(env, {"blocks": [1, 3], "weights": [0, 1]}, "s") == 2
    one = R.window(env, 1, 170)
    assert U.blocks_overlapping(env, one["start"], one["end"]) == [0, 1]
    two = R.window(env, 2, 170)
    assert U.blocks_overlapping(env, two["start"], two["end"]) == [0, 1, 2, 3]
    assert R.traces_covered(env, two) == 4 * 120
    assert env.spans_covered(one) == 2 * 120 * 4


# ------------------------------------------------------------------ tenants
def test_tenants_get_their_blocks(in_tmp):
    tenants = [{"name": "single-tenant", "blocks": 3}, {"name": "tenant-b", "blocks": 2}]
    cfg = small(5, tenants=tenants)
    m = build(cfg)
    assert m["tenant"] == "single-tenant" and m["tenants"] == ["single-tenant", "tenant-b"]
    assert [b["tenant"] for b in m["blocks"]] == ["single-tenant"] * 3 + ["tenant-b"] * 2
    # each tenant's blocks start again at its newest window
    assert [b["window"] for b in m["blocks"]] == [0, 1, 2, 0, 1]
    store = os.path.join(m["path"], "store")
    assert sorted(os.listdir(store)) == ["single-tenant", "tenant-b"]
    for t in tenants:
        assert sorted(os.listdir(os.path.join(store, t["name"]))) == sorted(
            b["block_id"] for b in m["blocks"] if b["tenant"] == t["name"])
    env = env_of(cfg, m)
    assert [b["index"] for b in env.blocks()] == [0, 1, 2]
    assert [b["index"] for b in env.blocks("tenant-b")] == [3, 4]
    newest = U.window(env, 0)
    assert U.blocks_overlapping(env, newest["start"], newest["end"]) == [0]
    assert U.blocks_overlapping(env, newest["start"], newest["end"],
                                tenant="tenant-b") == [3]
    assert U.blocks_overlapping(env, 0, 2**40, tenant="nobody") == []
    assert env.spans_covered({**newest, "tenant": "tenant-b"}) == 120 * 4
    import random
    rnd = random.Random(1)
    assert {U.draw_block(rnd, env) for _ in range(50)} <= {0, 1, 2}
    assert {U.draw_block(rnd, env, "tenant-b") for _ in range(50)} == {3, 4}
    assert [[b["index"] for b in h] for h in R.hours(env, "tenant-b")] == [[3], [4]]


def test_one_tenant_keeps_the_manifests_shape(in_tmp):
    m = build(small(2))
    assert m["tenant"] == "single-tenant" and "tenants" not in m
    assert os.listdir(os.path.join(m["path"], "store")) == ["single-tenant"]
    # and a cached manifest from before the keys existed still reads
    old = json.loads(json.dumps(m))
    for b in old["blocks"]:
        del b["tenant"], b["window"]
    env = env_of(small(2), old)
    assert [b["index"] for b in env.blocks()] == [0, 1]
    assert len(R.hours(env)) == 2 and U.window(env, 1) == {
        "start": m["blocks"][1]["start_s"], "end": m["blocks"][1]["end_s"]}
    assert build(small(2)) == m  # the second run of a seed links the first


def test_the_fixture_builds_and_the_program_sees_both_tenants(in_tmp):
    """blocks 8, two a window, two tenants: one two-block level-0 job a
    window and tenant, and the db lists both tenants."""
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db.compactor import CompactorConfig, select_jobs
    from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig

    cfg = H.load_json(os.path.join(HERE, "fixtures", "fixture-2window-2tenant.json"))
    m = build(cfg)
    assert len(m["blocks"]) == 8 and m["tenants"] == ["single-tenant", "tenant-b"]
    storage = str(in_tmp / "storage")
    corpus.link_store(m, storage)
    db = TempoDB(TempoDBConfig(wal_path=str(in_tmp / "wal")),
                 backend=LocalBackend(storage))
    try:
        db.poll_now()
        assert sorted(db.tenants()) == ["single-tenant", "tenant-b"]
        for tenant in m["tenants"]:
            metas = db.blocklist.metas(tenant)
            assert len(metas) == 4
            jobs = select_jobs(tenant, metas, CompactorConfig())
            assert [len(j.blocks) for j in jobs] == [2, 2]
            windows = {b["block_id"]: b["window"] for b in m["blocks"]
                       if b["tenant"] == tenant}
            assert sorted({windows[x.block_id] for x in j.blocks} == {w}
                          for j, w in zip(jobs, (1, 0))) == [True, True]
    finally:
        db.close()


# ------------------------------------------------------------------ workers
@pytest.mark.parametrize("name", CONFIGS)
def test_the_benchmarks_configurations_build_every_block_at_once(name):
    """Neither a cached nor a first-of-seed set-up of a cell may move because
    its workers were batched: all four configurations resolve to all their
    blocks, on any host (the bound is fixed, not a share of what is free)."""
    sz = corpus.sizes(config(name), "full")
    assert corpus.resolve_workers(sz) == sz["blocks"]


def test_twelve_full_width_blocks_build_in_batches():
    sz = corpus.sizes(config("chip1-4block"), "full")
    sz["blocks"] = 12
    n = corpus.resolve_workers(sz)
    assert n == 8  # 8 + 4: what the chip machine's host built in 43 s (PERF.md)
    cost = corpus.WORKER_BYTES_BASE + corpus.WORKER_BYTES_PER_SPAN * 150000 * 69
    assert n * cost <= corpus.BUILD_MEM_CAP < (n + 1) * cost
    huge = {**sz, "traces_per_block": 150000 * 20}
    assert corpus.resolve_workers(huge) == 1  # never none


def test_the_worker_count_does_not_follow_the_host(monkeypatch):
    """The driver runs parent and change on one machine: what else that
    machine is doing may not change a cell's worker count."""
    sz = corpus.sizes(config("chip1-32hourly"), "full")
    real_open = open

    def no_meminfo(path, *a, **k):
        assert path != "/proc/meminfo"
        return real_open(path, *a, **k)

    monkeypatch.setattr("builtins.open", no_meminfo)
    assert corpus.resolve_workers(sz) == 32


@pytest.mark.parametrize("limit", [1, 3, 12])
def test_the_pool_keeps_to_its_bound(limit):
    alive, most, lock = [0], [0], threading.Lock()
    full = threading.Barrier(limit)  # passes only with `limit` alive at once

    def worker(argv):
        with lock:
            alive[0] += 1
            most[0] = max(most[0], alive[0])
        full.wait(timeout=30)
        with lock:
            alive[0] -= 1
        return 0

    rcs = corpus.run_pool([["w", str(i)] for i in range(12)], limit, run=worker)
    assert rcs == [0] * 12 and most[0] == min(limit, 12)


def test_a_failing_worker_fails_the_build_and_removes_it(in_tmp, monkeypatch):
    started = []

    def call(argv, cwd=None):
        started.append(argv[argv.index("--block") + 1])
        return 1 if started[-1] == "1" else 0

    monkeypatch.setattr(corpus.subprocess, "call", call)
    cfg = small(6)
    monkeypatch.setattr(corpus, "resolve_workers", lambda sz: 2)
    with pytest.raises(RuntimeError, match="corpus workers exited"):
        build(cfg)
    assert os.listdir(os.path.join(str(in_tmp), "corpus")) == []
    assert len(started) < 6  # nothing new starts once one has failed


# ------------------------------------------------------------------ warm-up
@pytest.mark.parametrize("max_parallel,expect", [(None, 6), (2, 2), (10, 6)])
def test_per_block_warm_up_runs_max_parallel_blocks_side_by_side(
        monkeypatch, max_parallel, expect):
    alive, most, lock, sent = [0], [0], threading.Lock(), []
    full = threading.Barrier(expect)  # passes only with `expect` side by side

    class FakeClient:
        def __init__(self, port, timeout=0):
            with lock:
                alive[0] += 1
                most[0] = max(most[0], alive[0])
            full.wait(timeout=30)

        def close(self):
            with lock:
                alive[0] -= 1

    def fake_send(op, env, client, phase, due=None):
        with lock:
            sent.append((op["shape"], op.get("block")))
        return H.result_record(op, phase, 200, 0.0, 0.0)

    monkeypatch.setattr(H, "Client", FakeClient)
    monkeypatch.setattr(H, "send", fake_send)
    manifest = {"tenant": "single-tenant", "blocks": [
        {"index": b, "tenant": "single-tenant" if b < 6 else "tenant-b",
         "window": b % 6, "n_traces": 10, "n_spans": 40,
         "start_s": 1000 * b, "end_s": 1000 * b + 900} for b in range(8)]}
    cfg = small(8)
    env = H.Env(cfg, {"name": "t"}, manifest, 1)
    step = {"step": "per_block", "shapes": [{"shape": "find_hit"}, {"shape": "find_miss"}]}
    if max_parallel:
        step["max_parallel"] = max_parallel
    out = H.warm_up({}, {"name": "t", "warmup": [step]}, env, 0, lambda: {})
    assert most[0] == expect and alive[0] == 0
    # every shape once over every block of the tenant addressed, no other
    hits = sorted(b for s, b in sent if s == "find_hit")
    assert hits == list(range(6)) and len(out) == len(sent) == 12
    assert env.force_block is None
