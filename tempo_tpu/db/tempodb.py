"""TempoDB: the storage-engine facade (reader / writer / compactor).

The role of tempodb.New + Reader/Writer/Compactor interfaces in the
reference (tempodb/tempodb.go:68-197): backend selection, WAL, blocklist
+ polling, parallel multi-block Find, per-block Search fan-out, and the
compaction/retention drivers. Services (L5) sit on top of this facade;
everything below it is columnar blocks + device kernels.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field

import numpy as np

from ..backend import open_backend
from ..backend.base import RawBackend
from ..block.builder import build_block_from_traces
from ..block.meta import BlockMeta
from ..block.reader import BackendBlock
from ..util.distinct import DistinctStringCollector
from ..wire.combine import combine_traces
from ..wire.model import Trace
from . import compactor as comp
from .blocklist import Blocklist, Poller
from .search import SearchRequest, SearchResponse, search_block, search_tag_values, search_tags
from .wal import WAL


@dataclass
class TempoDBConfig:
    backend: dict = field(default_factory=lambda: {"backend": "local", "path": "./tempo-data"})
    wal_path: str = "./tempo-wal"
    row_group_spans: int = 1 << 16
    # chunk codec for ingest-written blocks (colio codec matrix:
    # zstd | gzip | lzma | raw); compaction output uses compaction.zstd_level
    block_codec: str = "zstd"
    pool_workers: int = 8
    blocklist_poll_s: float = 15.0
    block_cache_blocks: int = 64
    search_default_limit: int = 20
    device_find: bool = True  # batched/sharded device Find (ops/find, parallel/find)
    device_search: bool = True  # stacked multi-block device search (parallel/search)
    # searches of a block before its columns are staged on device (first
    # touches run the zero-RTT host engine; db/route reads it off the readers)
    device_promote_touches: int = 2
    # cross-query batching executor (db/batchexec): None fields resolve
    # from the TEMPO_BATCH / TEMPO_BATCH_WINDOW_MS / TEMPO_BATCH_MAX env
    batch_enabled: bool | None = None
    batch_window_ms: float | None = None
    batch_max: int | None = None
    compaction: comp.CompactorConfig = field(default_factory=comp.CompactorConfig)


def _raised(r):
    if isinstance(r, Exception):
        raise r
    return r


class TempoDB:
    def __init__(self, cfg: TempoDBConfig, backend: RawBackend | None = None):
        self.cfg = cfg
        # chaos seam: in an armed process (TEMPO_CHAOS / --chaos.rules)
        # every backend op runs through the fault-injection wrapper;
        # unarmed processes get the raw backend with zero indirection
        from ..chaos.backendwrap import maybe_wrap

        self.backend = maybe_wrap(backend or open_backend(cfg.backend))
        os.makedirs(cfg.wal_path, exist_ok=True)
        self.wal = WAL(os.path.join(cfg.wal_path, "wal"))
        self.blocklist = Blocklist()
        self.poller = Poller(self.backend)
        # context-propagating: pooled engine legs keep the caller's
        # ambient self-trace + affinity placement (util/ctxpool)
        from ..util.ctxpool import ContextThreadPool

        self.pool = ContextThreadPool(max_workers=cfg.pool_workers)
        # fan-out pool for the query engines: on a 1-core box with a
        # LOCAL backend the handoffs only add GIL ping-pong (~20% of a
        # cold scan), so every engine gets None and runs serial; remote
        # backends keep the pool (IO waits release the GIL and overlap)
        self.io_pool = (
            self.pool
            if (os.cpu_count() or 2) > 1 or getattr(self.backend, "is_remote", True)
            else None
        )
        self._block_cache: dict[tuple[str, str], BackendBlock] = {}
        self._cache_lock = threading.Lock()
        self._poll_thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._mesh = None
        # cross-query batching: concurrent search / find jobs that share
        # a coalesce key merge into one fused kernel launch (batchexec)
        from .batchexec import QueryBatchers

        self.batchers = QueryBatchers(
            enabled=cfg.batch_enabled, window_ms=cfg.batch_window_ms,
            max_batch=cfg.batch_max, mesh_fn=self._batch_mesh)
        # compaction ownership + dedupe hooks, overridden by the service layer
        self.owns_job = lambda job_hash: True
        from ..util.metrics import Counter, Histogram

        self.poll_duration = Histogram("tempo_blocklist_poll_duration_seconds")
        self.poll_errors = Counter("tempo_blocklist_poll_errors_total")
        self.polls = Counter("tempo_blocklist_polls_total")
        # measured-crossover routing: seed the cold-scan host-rate EMA
        # from the persisted CostLedger (util/costledger) once
        from .route import seed_host_rate_from_ledger

        seed_host_rate_from_ledger()

    def _batch_mesh(self):
        """Mesh handed to the batching executors' window leaders
        (db/batchexec -> parallel/multiquery): all visible chips, or
        None on a single chip / with device search off -- the
        single-chip fused launch is already optimal there."""
        if not self.cfg.device_search:
            return None
        mesh = self.mesh
        return mesh if mesh.devices.size > 1 else None

    @property
    def mesh(self):
        """Device mesh for the sharded Find/search paths (all visible
        chips; a single chip yields a 1x1 mesh so the same mesh program
        the multi-chip dryrun validates also serves single-chip)."""
        if self._mesh is None:
            from ..parallel import make_mesh

            self._mesh = make_mesh()
        return self._mesh

    # ------------------------------------------------------------ blocks
    def open_block(self, meta: BlockMeta) -> BackendBlock:
        key = (meta.tenant_id, meta.block_id)
        with self._cache_lock:
            blk = self._block_cache.get(key)
            if blk is None:
                from ..block.versioned import open_block_versioned

                blk = open_block_versioned(self.backend, meta)
                # cached readers are long-lived over immutable blocks:
                # mark them device-worthy so db/route's auto mode stages
                # (and keeps) their columns on the accelerator
                blk.device_pinned = self.cfg.device_search
                blk.promote_touches = self.cfg.device_promote_touches
                if len(self._block_cache) >= self.cfg.block_cache_blocks:
                    self._block_cache.pop(next(iter(self._block_cache)))
                self._block_cache[key] = blk
            return blk

    def write_block(self, tenant: str, traces: list[tuple[bytes, Trace]]) -> BlockMeta:
        """Build + flush a complete block from sorted traces (ingester's
        CompleteBlock + WriteBlock path, tempodb.go:199-251)."""
        meta = build_block_from_traces(
            self.backend, tenant, traces, row_group_spans=self.cfg.row_group_spans,
            codec=self.cfg.block_codec,
        )
        self.blocklist.update(tenant, add=[meta])
        return meta

    # ------------------------------------------------------------- find
    def find_candidates(
        self, tenant: str, trace_id: bytes, time_start: int = 0, time_end: int = 0
    ) -> list[BlockMeta]:
        """Blocks whose id range + time window may hold the trace (the
        unit the frontend's ID-space sharder partitions)."""
        hex_id = trace_id.rjust(16, b"\x00").hex()
        return [
            m
            for m in self.blocklist.metas(tenant)
            if m.may_contain_id(hex_id) and m.overlaps_time(time_start, time_end)
        ]

    def find_trace_by_id(
        self, tenant: str, trace_id: bytes, time_start: int = 0, time_end: int = 0
    ) -> Trace | None:
        """Parallel candidate-block lookup + combine
        (reference: tempodb.Find, tempodb/tempodb.go:271-352)."""
        candidates = self.find_candidates(tenant, trace_id, time_start, time_end)
        return self.find_in_blocks(tenant, trace_id, candidates)

    def find_in_blocks(
        self, tenant: str, trace_id: bytes, candidates: list[BlockMeta]
    ) -> Trace | None:
        """Lookup restricted to an explicit block set -- one frontend
        ID-shard job (tracebyidsharding.go:30-48 analog: the frontend
        partitions the candidate blocks, we execute one partition)."""
        if not candidates:
            return None
        if self.cfg.device_find and self.batchers.enabled:
            # concurrent lookups against the same candidate partition
            # share one batched bisection (the Q axis of ops/find)
            from .batchexec import batched_find

            return batched_find(self.batchers.find, self, candidates, trace_id)
        if self.cfg.device_find:
            found = self._device_find(candidates, trace_id)
        else:
            results = list(
                self.pool.map(lambda m: self.open_block(m).find_trace_by_id(trace_id), candidates)
            )
            found = [t for t in results if t is not None]
        if not found:
            return None
        return combine_traces(found)

    def find_in_blocks_multi(self, items: list) -> list:
        """Many (tenant, trace_id, candidates) lookups at once: jobs
        sharing a candidate partition submit to the find batcher as one
        group from this thread (and merge with any window-mates)."""
        from .batchexec import _FindItem

        out: list = [None] * len(items)
        groups: dict[tuple, list[tuple[int, object]]] = {}
        for i, (tenant, trace_id, candidates) in enumerate(items):
            if not candidates:
                continue
            if not (self.cfg.device_find and self.batchers.enabled):
                out[i] = self.find_in_blocks(tenant, trace_id, candidates)
                continue
            key = ("find", candidates[0].tenant_id,
                   tuple(m.block_id for m in candidates))
            groups.setdefault(key, []).append((i, _FindItem(
                metas=candidates, trace_id=trace_id, db=self)))
        for key, pairs in groups.items():
            results = self.batchers.find.submit_many(key, [it for _, it in pairs])
            for (i, _), r in zip(pairs, results):
                out[i] = r
        return out

    def _device_find(self, candidates: list[BlockMeta], trace_id: bytes) -> list[Trace]:
        """Device Find: host bloom gate (one ranged read per block), then
        ONE batched bisection kernel over every surviving block's sorted
        id index — sharded over the mesh when >1 chip is attached. Each
        block reports its own hit row so partial traces combine, the
        device analog of the reference's per-block fan-out + combiner
        (tempodb/tempodb.go:271-352)."""
        from ..block import schema as S
        from ..ops.find import lookup_ids_blocks_cached
        from ..parallel.find import sharded_find_rows
        from ..util.kerneltel import TEL

        with TEL.stage("find:bloom", blocks=len(candidates)):
            blocks = [self.open_block(m) for m in candidates]
            gates = list(self.pool.map(lambda b: b.bloom_test(trace_id), blocks))
            blocks = [b for b, ok in zip(blocks, gates) if ok]
        if not blocks:
            return []
        query = np.asarray(
            [S.trace_id_to_codes(trace_id.rjust(16, b"\x00"))], dtype=np.int32
        )
        with TEL.stage("find:lookup", blocks=len(blocks)):
            if self.mesh.devices.size > 1:
                codes = list(self.pool.map(lambda b: b.trace_index["trace.id_codes"], blocks))
                sids = sharded_find_rows(self.mesh, codes, query)
            else:
                # single chip: lookup_ids_blocks_cached auto-routes to the
                # host searchsorted engine (zero device round trips)
                list(self.pool.map(lambda b: b.trace_index, blocks))  # parallel IO
                sids = lookup_ids_blocks_cached(blocks, query)
        hits = [(blk, int(sid)) for blk, sid in zip(blocks, sids[:, 0]) if sid >= 0]
        with TEL.stage("find:fetch", hits=len(hits)):
            return list(self.pool.map(lambda h: h[0].materialize_traces([h[1]])[0], hits))

    # ------------------------------------------------------------ search
    def search(self, tenant: str, req: SearchRequest) -> SearchResponse:
        metas = [m for m in self.blocklist.metas(tenant) if m.overlaps_time(req.start, req.end)]
        return self.search_blocks(tenant, metas, req)

    def search_blocks(self, tenant: str, metas: list[BlockMeta], req: SearchRequest) -> SearchResponse:
        """Search a set of blocks as one unit -- the execution engine
        behind both TempoDB.search and the frontend's block-batch jobs."""
        return _raised(self._run_search_jobs([(metas, req, None)])[0])

    def search_block_shard(self, tenant: str, meta: BlockMeta, req: SearchRequest, groups_range) -> SearchResponse:
        """One sharded search job (frontend's StartPage/TotalPages analog)."""
        return _raised(self._run_search_jobs([([meta], req, groups_range)], shard=True)[0])

    def search_block_shard_multi(self, items: list) -> list:
        """Many (tenant, meta, req, groups_range) shard jobs at once."""
        return self._run_search_jobs(
            [([m], req, groups) for _, m, req, groups in items], shard=True)

    def search_blocks_multi(self, items: list) -> list:
        """Many (tenant, metas, req) search jobs at once -- the frontend's
        batch-aware dequeue hands a whole burst here so even a single
        worker thread forms full fused batches."""
        return self._run_search_jobs([(metas, req, None) for _, metas, req in items])

    def _run_search_jobs(self, jobs: list, shard: bool = False) -> list:
        """The one search job entry: (metas, req, groups_range) jobs, all
        row-group shard jobs (one block each) or all block-set jobs.
        One-block jobs go to the batch window (db/batchexec), where
        concurrent ones against one hot block coalesce into one fused
        launch; a job it refuses, and any other, runs `outside` under the
        plan the window made, if it made one. Returns a SearchResponse a
        job or, where the window ran a job that failed, its Exception."""
        from .batchexec import batched_search_block_many

        def outside(blocks, req, groups, planned=None):
            if shard:
                return search_block(blocks[0], req, groups_range=groups, planned=planned)
            return self._search_block_set(blocks, req, planned)

        jobs = [([self.open_block(m) for m in metas], req, groups)
                for metas, req, groups in jobs]
        out: list = [None] * len(jobs)
        if self.cfg.device_search and self.batchers.enabled:
            ones = [i for i, (blocks, _, _) in enumerate(jobs) if len(blocks) == 1]
            got = batched_search_block_many(
                self.batchers.search,
                [(jobs[i][0][0], *jobs[i][1:]) for i in ones],
                default_limit=None if shard else self.cfg.search_default_limit,
                refused=lambda blk, *rest: outside([blk], *rest))
            for i, r in zip(ones, got):
                out[i] = r
        return [outside(*job) if r is None else r for job, r in zip(jobs, out)]

    def _search_block_set(self, blocks: list[BackendBlock], req: SearchRequest,
                          planned=None) -> SearchResponse:
        """A block-set job outside the batch window. Single chip: fused
        per-block kernels + ONE cross-block device top-k sync
        (search_blocks_fused). Mesh: the stacked sharded program
        (parallel/search.py). Per-block search when the device budget or
        plan shape demands it. planned: a one-block job's plan."""
        resp = SearchResponse()
        if not blocks:
            return resp
        limit = req.limit or self.cfg.search_default_limit
        if self.cfg.device_search:
            if self.mesh.devices.size > 1 and len(blocks) > 1:
                from .search import search_blocks_device

                got = search_blocks_device(
                    blocks, req, self.mesh,
                    default_limit=self.cfg.search_default_limit, pool=self.io_pool,
                )
            else:
                from .search import search_blocks_fused

                got = search_blocks_fused(
                    blocks, req, pool=self.io_pool,
                    default_limit=self.cfg.search_default_limit,
                    plans=None if planned is None else [planned],
                )
            if got is not None:  # None -> oversize / plan-shape fallback
                return got

        def one(blk):
            return search_block(blk, req, planned=planned)

        for r in (self.io_pool.map(one, blocks) if self.io_pool is not None
                  else map(one, blocks)):
            resp.merge(r, limit)
            if len(resp.traces) >= limit:
                break
        resp.traces.sort(key=lambda t: -t.start_time_unix_nano)
        return resp

    # ------------------------------------------------------------ metrics
    def metrics_query_range(self, tenant: str, req) -> "object":
        """TraceQL metrics range query over the backend blocklist
        (db/metrics_exec): per-block fused filter->bucketize->fold on
        device or host by temperature, partial series merged by label;
        the stacked mesh fold takes over on multi-chip."""
        from .metrics_exec import MetricsRequest, metrics_query_range_blocks

        assert isinstance(req, MetricsRequest)
        start_s, end_s = req.start_ms // 1000, -(-req.end_ms // 1000)
        metas = [m for m in self.blocklist.metas(tenant)
                 if m.overlaps_time(start_s, end_s)]
        blocks = [self.open_block(m) for m in metas]
        mesh = (self.mesh if self.cfg.device_search
                and self.mesh.devices.size > 1 else None)
        return metrics_query_range_blocks(
            blocks, req, pool=self.io_pool, mesh=mesh)

    def search_tags(self, tenant: str, max_bytes: int = 0) -> list[str]:
        c = DistinctStringCollector(max_bytes)
        for m in self.blocklist.metas(tenant):
            search_tags(self.open_block(m), c)
        return c.strings()

    def search_tag_values(self, tenant: str, tag: str, max_bytes: int = 0) -> list[str]:
        c = DistinctStringCollector(max_bytes)
        for m in self.blocklist.metas(tenant):
            search_tag_values(self.open_block(m), tag, c)
        return c.strings()

    # ----------------------------------------------------------- polling
    def poll_now(self) -> None:
        from ..util.metrics import timed

        self.polls.inc()
        with timed(self.poll_duration):
            metas, compacted = self.poller.poll()
        self.blocklist.apply_poll_results(metas, compacted)
        with self._cache_lock:  # drop cached readers for vanished blocks
            live = {(t, m.block_id) for t in metas for m in metas[t]}
            for key in [k for k in self._block_cache if k not in live]:
                self._block_cache.pop(key, None)

    def enable_polling(self) -> None:
        if self._poll_thread:
            return

        def loop():
            while not self._stop.wait(self.cfg.blocklist_poll_s):
                try:
                    self.poll_now()
                except Exception:  # noqa: BLE001 - poll errors keep last list
                    self.poll_errors.inc()

        self.poll_now()
        self._poll_thread = threading.Thread(target=loop, daemon=True, name="blocklist-poller")
        self._poll_thread.start()

    # --------------------------------------------------------- compaction
    def _apply_compaction_result(self, tenant: str, res: comp.CompactionResult,
                                 metas_by_id: dict[str, BlockMeta]) -> None:
        """Apply one job's result to the blocklist -- shared by the
        sequential (compact_once) and pipelined (compact_tenants) sweeps
        so their post-job state can't drift."""
        removed = set(res.compacted_ids)
        self.blocklist.update(
            tenant,
            add=res.new_blocks,
            remove=list(removed),
            add_compacted=[m for bid, m in metas_by_id.items() if bid in removed],
        )

    def compact_once(self, tenant: str) -> list[comp.CompactionResult]:
        """One compaction sweep for a tenant: select jobs, run owned ones."""
        metas = self.blocklist.metas(tenant)
        metas_by_id = {m.block_id: m for m in metas}
        jobs = comp.select_jobs(tenant, metas, self.cfg.compaction)
        results = []
        for job in jobs:
            if not self.owns_job(job.hash):
                continue
            res = comp.compact(self.backend, job, self.cfg.compaction)
            self._apply_compaction_result(tenant, res, metas_by_id)
            results.append(res)
        return results

    def compact_tenants(self, tenants: list[str] | None = None) -> list:
        """Concurrent compaction sweep across tenants through the
        pipeline executor (db/compact_pipeline): select owned jobs per
        tenant, run them with TEMPO_COMPACT_CONCURRENCY workers under the
        host-RAM admission budget (per-tenant round-robin admission),
        and apply each job's blocklist update the moment it commits --
        exactly the update compact_once makes, from the worker thread
        (Blocklist.update is lock-guarded). Returns the pipeline's
        JobOutcome list; per-job errors ride in the outcomes rather than
        aborting the sweep."""
        from .compact_pipeline import CompactionPipeline

        if tenants is None:
            tenants = self.tenants()
        jobs_by_tenant: dict[str, list[comp.CompactionJob]] = {}
        metas_by_tenant: dict[str, dict[str, BlockMeta]] = {}
        for tenant in tenants:
            metas = self.blocklist.metas(tenant)
            jobs = [j for j in comp.select_jobs(tenant, metas, self.cfg.compaction)
                    if self.owns_job(j.hash)]
            if jobs:
                jobs_by_tenant[tenant] = jobs
                metas_by_tenant[tenant] = {m.block_id: m for m in metas}

        def on_result(tenant: str, job: comp.CompactionJob,
                      res: comp.CompactionResult) -> None:
            self._apply_compaction_result(tenant, res, metas_by_tenant[tenant])

        pipeline = CompactionPipeline(self.backend, self.cfg.compaction)
        return pipeline.run(jobs_by_tenant, on_result=on_result)

    def retention_once(self, tenant: str) -> comp.RetentionResult:
        res = comp.apply_retention(
            self.backend,
            tenant,
            self.blocklist.metas(tenant),
            self.blocklist.compacted_metas(tenant),
            self.cfg.compaction,
            owns=self.owns_job,
        )
        if res.marked:
            self.blocklist.update(tenant, remove=res.marked)
        return res

    def tenants(self) -> list[str]:
        return self.blocklist.tenants()

    def close(self) -> None:
        self._stop.set()
        if self._poll_thread:
            self._poll_thread.join(timeout=2)
        self.pool.shutdown(wait=False)
