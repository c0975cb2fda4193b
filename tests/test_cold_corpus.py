"""A corpus three times the staged budget, through TempoDB's normal search and
metrics path (what `chip1-12block-read-cold` runs on the chip, here on the
CPU at the tiny scale): twelve blocks of `benchmarks/configs/chip1-12block.json`,
the five shapes and the block popularity of `benchmarks/mixes/read-cold.json`,
four threads, the staged cache held to about a third of what the shapes stage.
Every answer is held to the benchmark's own oracle by the shapes' own checks;
eviction, demotion to the host chunk pool and the restage from it are counted.
Counts and equality only."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import urllib.parse

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import corpus, harness as H  # noqa: E402
from tempo_tpu.ops import chunkpool, stage  # noqa: E402

BENCH = os.path.join(ROOT, "benchmarks")
TENANT = corpus.TENANT
SEED = 2149000461
REQUESTS = 320


def _build_in_process(argv, cwd=None):
    """A corpus worker's command line, run here: twelve tiny blocks do not
    need twelve interpreters."""
    kv = dict(zip(argv[2::2], argv[3::2]))
    corpus.build_block(argparse.Namespace(**{
        k[2:].replace("-", "_"): v if k in ("--out", "--tenant") else int(v)
        for k, v in kv.items()}))
    return 0


class Cold:
    """The corpus, a TempoDB over it and the benchmark's view of both."""

    def __init__(self, tmp: str, mp: pytest.MonkeyPatch):
        from tempo_tpu.backend.local import LocalBackend
        from tempo_tpu.db import route
        from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig

        mp.setattr(corpus, "bench_dir", lambda *parts: os.path.join(tmp, *parts))
        mp.setattr(corpus.subprocess, "call", _build_in_process)
        # tiny blocks: a host scan always looks cheaper than a round trip.
        # The deployment's blocks are a thousand times these, so steer the
        # estimate, not the program: no link round trip to win against
        mp.setattr(route, "link_rtt_ms", lambda: 0.0)
        self.config = H.load_json(os.path.join(BENCH, "configs", "chip1-12block.json"))
        self.mix = H.load_json(os.path.join(BENCH, "mixes", "read-cold.json"))
        self.manifest = corpus.ensure(self.config, "tiny", SEED, log=lambda m: None)
        storage = os.path.join(tmp, "storage")
        corpus.link_store(self.manifest, storage)
        self.db = TempoDB(TempoDBConfig(wal_path=os.path.join(tmp, "wal")),
                          backend=LocalBackend(storage))
        self.db.poll_now()
        self.stream = next(s for s in self.mix["streams"] if s["name"] == "search")

    def env(self, seed: int = SEED) -> H.Env:
        return H.Env(self.config, self.mix, self.manifest, seed)

    def answer(self, op: dict, env: H.Env) -> tuple[bool, str, bytes]:
        """The request a shape would send over HTTP, asked of the db, and
        the shape's own check of the body the server would have written."""
        from tempo_tpu.db.metrics_exec import align_params, to_prometheus
        from tempo_tpu.db.search import SearchRequest

        mod = H.load_plugin("shapes", op["shape"])
        _, path, _, _ = mod.request(op, env)
        url = urllib.parse.urlsplit(path)
        q = {k: v[0] for k, v in urllib.parse.parse_qs(url.query).items()}
        if url.path == "/api/metrics/query_range":
            req = align_params(q["q"], float(q["start"]), float(q["end"]),
                               float(q["step"]))
            body = to_prometheus(self.db.metrics_query_range(TENANT, req))
        else:
            tags = dict(kv.split("=", 1) for kv in q.get("tags", "").split() if kv)
            resp = self.db.search(TENANT, SearchRequest(
                query=q.get("q", ""), tags=tags, limit=int(q["limit"]),
                start=int(q["start"]), end=int(q["end"])))
            body = {"traces": [t.to_dict() for t in resp.traces]}
        data = json.dumps(body).encode()
        ok, detail = mod.check(op, 200, data, env)
        return ok, detail, data

    def per_block(self, env: H.Env) -> list[dict]:
        """Warm-up's `per_block` step: every shape of the mix over every block."""
        import random

        rnd = random.Random(f"{env.seed}-warm")
        ops = []
        for spec in self.stream["shapes"]:
            mod = H.load_plugin("shapes", spec["shape"])
            for b in range(len(self.manifest["blocks"])):
                env.force_block = b
                op = mod.build(rnd, env, spec.get("params", {}))
                op.update(shape=spec["shape"], i=-1)
                ops.append(op)
        env.force_block = None
        return ops

    def run(self, ops: list[dict], env: H.Env, threads: int = 4) -> list[tuple]:
        out, lock, todo = [], threading.Lock(), iter(ops)

        def loop():
            while True:
                with lock:
                    op = next(todo, None)
                if op is None:
                    return
                try:
                    res = self.answer(op, env)
                except Exception as e:  # a request that raises is a failure
                    res = (False, f"{type(e).__name__}: {e}", b"")
                with lock:
                    out.append((op, *res))

        ts = [threading.Thread(target=loop) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return out


@pytest.fixture(scope="module")
def cold(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    budget = stage.staged_cache_stats()["budget_bytes"]
    chunkpool.clear()
    c = Cold(str(tmp_path_factory.mktemp("cold")), mp)
    try:
        yield c
    finally:
        c.db.close()
        mp.undo()
        stage.set_staged_cache_budget(budget)
        chunkpool.clear()
        gc.collect()


def _wrong(results) -> list[str]:
    return [f"{op['shape']} block {op['block']}: {detail}"
            for op, ok, detail, _ in results if not ok]


def _working_set(cold) -> int:
    """Bytes the five shapes keep staged over the twelve blocks when nothing
    has to leave: every (shape, block) twice, since a whole-block job stages
    a reader from its second touch on (db/route._worth_staging)."""
    stage.set_staged_cache_budget(4 << 30)
    env = cold.env()
    for _ in range(2):
        assert _wrong(cold.run(cold.per_block(env), env)) == []
    return stage.staged_cache_stats()["bytes"]


def test_the_corpus_is_the_configurations(cold):
    blocks = cold.manifest["blocks"]
    assert len(blocks) == 12 and cold.config["reduced"] == []
    assert len(cold.db.blocklist.metas(TENANT)) == 12
    pop = cold.config["corpus"]["block_popularity"]
    assert len(pop) == 12 and abs(sum(pop) - 1.0) < 1e-9
    assert sum(pop[:2]) == pytest.approx(0.75)


def test_every_seed_sends_the_same_shape_and_block_list(cold):
    """`lib/coldutil.py`: inside each shape the next block is the one
    furthest behind the configuration's popularity, so two seeds differ in
    operands and not in how many misses they drew."""
    lists = {}
    for seed in (SEED, SEED + 7, 11):
        env = cold.env(seed)
        ops = H.build_ops(cold.mix["name"], cold.stream, env, n=240)
        lists[seed] = [(op["shape"], op["block"]) for op in ops]
        assert env.force_block is None
    first, *others = lists.values()
    assert all(o == first for o in others)
    operands = [{k: v for k, v in op.items() if k in ("key", "val", "us", "svc", "ms")}
                for seed in (SEED, 11)
                for op in H.build_ops(cold.mix["name"], cold.stream, cold.env(seed), n=40)]
    assert operands[:40] != operands[40:]
    pop = cold.config["corpus"]["block_popularity"]
    for shape in {s for s, _ in first}:
        blocks = [b for s, b in first if s == shape]
        for b in range(12):  # never more than one request off its share
            assert abs(blocks.count(b) - pop[b] * len(blocks)) <= 1.0, (shape, b)
    # warm-up's per_block step still reaches every (shape, block) pair
    assert {(op["shape"], op["block"]) for op in cold.per_block(cold.env())} == {
        (s, b) for s in {s for s, _ in first} for b in range(12)}


def test_cold_traffic_answers_as_the_oracle_and_counts_its_evictions(cold, monkeypatch):
    whole = _working_set(cold)
    assert whole > 0
    demoted = []
    real_demote = chunkpool.demote

    def demote(block_id, key, arr):
        demoted.append(int(arr.nbytes))
        return real_demote(block_id, key, arr)

    monkeypatch.setattr(chunkpool, "demote", demote)
    before = stage.staged_cache_stats()
    pool_before = chunkpool.stats()
    stage.set_staged_cache_budget(whole // 3)
    env = cold.env()
    ops = H.build_ops(cold.mix["name"], cold.stream, env, n=REQUESTS)
    # the mix's popularity: three quarters of the draws on the newest two
    on_newest = sum(op["block"] < 2 for op in ops)
    assert 0.65 * REQUESTS < on_newest < 0.85 * REQUESTS
    assert {op["block"] for op in ops} == set(range(12))
    results = cold.run(ops, env, threads=4)
    assert len(results) == REQUESTS and _wrong(results) == []
    after = stage.staged_cache_stats()
    assert after["bytes"] <= whole // 3 < whole
    evictions = after["evictions"] - before["evictions"]
    assert evictions > 0 and evictions == len(demoted)
    # every column the LRU popped was handed to the pool with its array
    assert after["evicted_bytes"] - before["evicted_bytes"] == sum(demoted)
    pool = chunkpool.stats()
    assert pool["demotions"] > pool_before["demotions"]
    assert pool["hits"] > pool_before["hits"]  # and some came back from it
    from tempo_tpu.util.kerneltel import TEL

    table = TEL.stage_stats()
    assert table["stage:demote"]["count"] > 0
    assert "cache:chunk-hit" in table and "stage:restage" not in table
    assert TEL.staged_cache_evictions.get() == after["evictions"]


def _evict_everything(cold, budget: int) -> None:
    stage.set_staged_cache_budget(1)  # all but the newest column leave
    stage.set_staged_cache_budget(budget)


def test_a_restage_from_the_pool_answers_as_one_from_the_backend(cold):
    budget = max(_working_set(cold) // 3, 1)
    stage.set_staged_cache_budget(budget)
    env = cold.env(SEED + 1)
    op = next(o for o in cold.per_block(env)
              if o["shape"] == "attr_eq_cold" and o["block"] == 7)
    assert cold.answer(op, env)[0]
    _evict_everything(cold, budget)  # the block's columns now sit in the pool
    h0 = chunkpool.stats()["hits"]
    ok_pool, detail, from_pool = cold.answer(op, env)
    assert ok_pool, detail
    assert chunkpool.stats()["hits"] > h0
    _evict_everything(cold, budget)
    chunkpool.clear()  # nothing to give back: read, assemble, upload again
    h1, m1 = chunkpool.stats()["hits"], chunkpool.stats()["misses"]
    ok_backend, detail, from_backend = cold.answer(op, env)
    assert ok_backend, detail
    assert chunkpool.stats()["hits"] == h1 and chunkpool.stats()["misses"] > m1
    assert from_pool == from_backend


def test_the_same_answers_without_the_host_pool(cold, monkeypatch):
    monkeypatch.setenv("TEMPO_CHUNK_CACHE", "0")
    chunkpool.clear()
    budget = max(_working_set(cold) // 3, 1)
    stage.set_staged_cache_budget(budget)
    before, pool_before = stage.staged_cache_stats(), chunkpool.stats()
    env = cold.env()
    ops = H.build_ops(cold.mix["name"], cold.stream, env, n=REQUESTS)
    results = cold.run(ops, env, threads=4)
    assert len(results) == REQUESTS and _wrong(results) == []
    after, pool = stage.staged_cache_stats(), chunkpool.stats()
    assert after["evictions"] > before["evictions"]
    assert pool["enabled"] is False and pool["entries"] == 0
    assert pool["demotions"] == pool_before["demotions"]
    assert pool["hits"] == pool_before["hits"]
