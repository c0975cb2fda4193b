"""App runtime: config root, module wiring, HTTP server, targets.

Reference: cmd/tempo/app -- module DAG (modules.go:360-414), single
binary running any role or `all` (config.go Target), HTTP API routes
(pkg/api/http.go:56-60). The single-binary target wires every module
in-process over an in-memory ring, exactly the topology the reference
uses for tests (cmd/tempo/main.go:186-194).

Run: python -m tempo_tpu.services.app --target=all --storage.path=DIR
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import sys
import threading
import time
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from ..db.search import SearchRequest
from ..db.tempodb import TempoDB, TempoDBConfig
from ..db.wal import WAL
from ..ring.ring import InMemoryKV, Lifecycler, Ring
from ..util.kerneltel import TEL
from ..util.traceid import parse_trace_id
from ..wire import otlp_json
from ..wire.model import Trace
from .compactor import Compactor
from .distributor import Distributor, PushError
from .frontend import Frontend, TooManyRequests
from .ingester import Ingester, IngesterConfig
from .overrides import Overrides
from .proctree import SCALABLE_TARGET
from .querier import Querier

DEFAULT_TENANT = "single-tenant"
TENANT_HEADER = "X-Scope-OrgID"  # reference: shared orgid header

INGESTER_RING = "ingester-ring"
COMPACTOR_RING = "compactor-ring"
GENERATOR_RING = "generator-ring"
QUERIER_RING = "querier-ring"  # blocklist-poll sharding (fleet/)


@dataclass
class AppConfig:
    target: str = "all"  # all | distributor | ingester | querier | ...
    http_port: int = 3200
    storage_path: str = "./tempo-data"
    wal_path: str = ""
    overrides_path: str = ""
    multitenancy: bool = False
    instance_id: str = ""  # empty = derive tempo-<http_port>
    replication_factor: int = 1
    ingester: IngesterConfig = field(default_factory=IngesterConfig)
    compaction_cycle_s: float = 30.0
    enable_generator: bool = True
    # multi-process topology: shared ring-KV directory + the address other
    # processes reach this one at (http://host:port). Empty = single binary
    # with an in-memory ring.
    kv_dir: str = ""
    # OR true multi-host membership: gossip bind addr (host:port, 0 port =
    # ephemeral) + comma-separated seed peers (reference: memberlist)
    gossip_bind: str = ""
    gossip_seeds: str = ""
    gossip_advertise: str = ""  # addr peers dial (wildcard binds need it)
    advertise_addr: str = ""
    http_host: str = ""  # default: loopback, or 0.0.0.0 when advertising non-loopback
    # shared secret for /internal/* and remote /flush//shutdown when the
    # server is reachable beyond loopback
    internal_token: str = ""
    # standalone querier: comma-separated frontend addresses to attach to
    # and pull jobs from (reference: querier.frontend-address)
    frontend_addr: str = ""
    frontend_workers: int = 8  # in-process worker threads (0 = dispatcher-only)
    # OTLP gRPC receiver port (reference receiver default 4317);
    # 0 = disabled, -1 = ephemeral (tests)
    otlp_grpc_port: int = 0
    # OpenCensus gRPC receiver port (reference shim.go:98; OC agent
    # convention 55678); 0 = disabled, -1 = ephemeral (tests)
    opencensus_grpc_port: int = 0
    # Jaeger gRPC collector port (reference shim.go:95-101; jaeger
    # collector convention 14250); 0 = disabled, -1 = ephemeral (tests)
    jaeger_grpc_port: int = 0
    # Jaeger agent UDP ports (client-SDK emitBatch; 6831 thrift-compact,
    # 6832 thrift-binary); 0 = disabled, -1 = ephemeral (tests). One
    # flag enables both sockets.
    jaeger_agent_port: int = 0
    # Kafka receiver (reference shim.go:100): host:port of a broker, ""
    # = disabled; messages are OTLP-proto ExportTraceServiceRequest
    kafka_brokers: str = ""
    kafka_topic: str = ""
    kafka_tenant: str = ""  # required when multitenancy is on
    # self-tracing: query operations emit spans into this tenant through
    # the local distributor ("" = off); reference: the app traces its own
    # handlers and ships them like any tenant's (SURVEY.md 5.1)
    self_tracing_tenant: str = ""
    # metrics-generator remote-write target ("" = expose on /metrics only)
    remote_write_url: str = ""
    remote_write_interval_s: float = 15.0
    # comma-separated serverless search endpoints: block-shard jobs POST
    # there with hedging, local execution as fallback (reference:
    # querier.search.external_endpoints, querier.go:401-458)
    search_external_endpoints: str = ""
    search_external_hedge_after_s: float = 4.0
    # persistent XLA compilation cache dir for config-file deployments;
    # applies only while JAX_COMPILATION_CACHE_DIR is unset ("" = the
    # fixed <checkout>/.jax_cache): restarts deserialize compiled
    # kernels from disk instead of re-paying the first-compile storm
    compile_cache_dir: str = ""
    # measured-crossover CostLedger artifact ("" = TEMPO_COST_LEDGER
    # env, else <storage_path>/cost_ledger.json): find/live-search/
    # block-scan routing seeds from it at startup (util/costledger)
    cost_ledger_path: str = ""
    # chaos plane (tempo_tpu/chaos): fault-injection rules as inline
    # JSON or a file path ("" = TEMPO_CHAOS env, else off). Armed
    # processes also accept runtime rule swaps via POST /internal/chaos.
    chaos_rules: str = ""
    # AOT warmup: compile the CostLedger's recorded (op, shape-bucket)
    # corpus through the persistent compile cache BEFORE serving, so
    # the first query stops paying the XLA compile storm (util/warmup)
    warmup_shapes: bool = False
    # fleet knobs (tempo_tpu/fleet): ring liveness window in seconds
    # (0 = ring.HEARTBEAT_TIMEOUT_S); lifecyclers also PRUNE peers past
    # it, so a SIGKILLed ingester leaves the write ring within about
    # one heartbeat period of the timeout instead of soaking doomed
    # replica writes until every reader's local filter catches up
    ring_heartbeat_timeout: float = 0.0
    # per-RPC deadline for remote ingester clients (replica writes,
    # quorum-read snapshots): the replica-leg timeout the quorum
    # arithmetic absorbs
    rpc_deadline_s: float = 10.0
    # standalone-querier worker threads against the frontend job API
    # (reference: querier.max-concurrent-queries)
    worker_concurrency: int = 4
    # --target scalable-single-binary: processes in the tree, one chip
    # each (0 = the chips the host shows); the replica count of the
    # reference's scalable deployment
    scalable_instances: int = 0


class App:
    """All modules of one process, wired per target."""

    VALID_TARGETS = ("all", SCALABLE_TARGET, "distributor", "ingester", "querier",
                     "query-frontend", "compactor", "metrics-generator")

    def __init__(self, cfg: AppConfig):
        # instance 0 of a scalable tree is the single binary plus a
        # supervisor of querier children; they reach its ingester through
        # a ring of the tree's own, made anew with every start
        single_binary = cfg.target in ("all", SCALABLE_TARGET)
        n_tree = (max(1, cfg.scalable_instances)
                  if cfg.target == SCALABLE_TARGET else 1)
        if n_tree > 1 and not (cfg.kv_dir or cfg.gossip_bind):
            import shutil

            cfg.kv_dir = os.path.join(cfg.storage_path, "scalable-ring")
            shutil.rmtree(cfg.kv_dir, ignore_errors=True)
        shared_ring = bool(cfg.kv_dir or cfg.gossip_bind)
        if cfg.target == "distributor" and not shared_ring:
            raise ValueError(
                "standalone distributor needs a shared ring (--kv.dir for a "
                "shared filesystem, --memberlist.bind/--memberlist.join for "
                "multi-host gossip) to reach remote ingesters; or run "
                "-target=all (single binary)"
            )
        if cfg.target not in self.VALID_TARGETS:
            raise ValueError(f"unknown target {cfg.target!r}; one of {self.VALID_TARGETS}")
        if not cfg.instance_id:
            cfg.instance_id = f"tempo-{cfg.http_port}"
        self.cfg = cfg

        # chaos plane: arm BEFORE any backend/TempoDB exists so the
        # object-store seam gets its injection wrapper; an explicit
        # --chaos.rules wins over (and replaces) the TEMPO_CHAOS env
        from ..chaos import plane as chaos_plane

        if cfg.chaos_rules:
            chaos_plane.configure_spec(cfg.chaos_rules)

        def has(role: str) -> bool:
            return single_binary or cfg.target == role

        if shared_ring and (single_binary or cfg.target == "ingester") and not cfg.advertise_addr.startswith(
            ("http://", "https://")
        ):
            raise ValueError(
                "an ingester joining a shared ring (--kv.dir or --memberlist.*) "
                "must advertise an http(s):// address (--advertise.addr) for "
                "peers to reach it"
            )
        # device cost plane wiring BEFORE the first TempoDB (it seeds
        # routing from the ledger at init): the device this process
        # got, persistent compile cache + the measured-crossover
        # CostLedger artifact. Explicit env vars win over the
        # storage-path default -- the operator aimed them.
        from ..util import costledger, costmodel

        # every target but the distributor launches kernels (block cut,
        # live engine, search/find/metrics, compaction bloom, generator
        # reduce): resolve the backend now so a missing chip stops the
        # start instead of serving from the host unannounced
        self.device = (costmodel.resolve_device()
                       if cfg.target != "distributor"
                       else costmodel.device_identity())
        if cfg.compile_cache_dir:
            costmodel.enable_compile_cache(cfg.compile_cache_dir)
        else:
            costmodel.enable_default_compile_cache()
        if not os.environ.get(costledger.LEDGER_ENV, ""):
            costledger.configure(
                cfg.cost_ledger_path
                or os.path.join(cfg.storage_path, "cost_ledger.json"))
        # continuous profiling plane (util/profiler): the bounded
        # profile-artifact store lives under the storage path (an
        # explicit TEMPO_PROFILE_DIR env wins inside configure)
        from ..util import profiler as _profiler

        _profiler.PROF.configure_artifacts(
            os.path.join(cfg.storage_path, "profiles"))

        # per-instance WAL dir: ingesters sharing --storage.path must never
        # replay (and delete) each other's live WAL files
        default_wal_layout = not cfg.wal_path
        wal_path = cfg.wal_path or os.path.join(cfg.storage_path, "wal", cfg.instance_id)
        self.db = TempoDB(
            TempoDBConfig(
                backend={"backend": "local", "path": cfg.storage_path},
                wal_path=os.path.join(cfg.storage_path, "db-wal"),
            )
        )
        self.db.poll_now()
        self.overrides = Overrides(path=cfg.overrides_path)
        if cfg.gossip_bind:
            from ..transport.gossip import GossipKV

            self.kv = GossipKV(
                cfg.gossip_bind,
                seeds=[s.strip() for s in cfg.gossip_seeds.split(",") if s.strip()],
                advertise=cfg.gossip_advertise,
            )
        elif cfg.kv_dir:
            from ..transport import FileKV

            self.kv = FileKV(cfg.kv_dir)
        else:
            self.kv = InMemoryKV()
        from ..ring.ring import HEARTBEAT_TIMEOUT_S

        hb_timeout = cfg.ring_heartbeat_timeout or HEARTBEAT_TIMEOUT_S
        # heartbeat fast enough that a live instance never looks dead
        # inside its own liveness window (harnesses run 2 s windows)
        hb_period = min(5.0, max(0.2, hb_timeout / 4.0))
        self._hb_timeout, self._hb_period = hb_timeout, hb_period
        self.ring = Ring(self.kv, INGESTER_RING,
                         replication_factor=cfg.replication_factor,
                         heartbeat_timeout=hb_timeout)

        # addr -> client: in-process registry + HTTP for remote addrs
        from ..transport import client_registry

        self._clients: dict[str, object] = {}
        self.client_for = client_registry(self._clients, token=cfg.internal_token,
                                          timeout=cfg.rpc_deadline_s)

        self.ingester = self.lifecycler = None
        if has("ingester"):
            self.ingester = Ingester(
                WAL(wal_path, fsync_interval_s=cfg.ingester.wal_fsync_interval_s),
                self.db, self.overrides, cfg.ingester)
            self.ingester.replay_wal()
            if default_wal_layout:
                # only the per-instance layout has meaningful siblings; an
                # explicit --wal.path may live beside unrelated directories
                self._warn_orphan_wals(os.path.dirname(wal_path), cfg.instance_id)
            self.lifecycler = Lifecycler(self.kv, INGESTER_RING, cfg.instance_id,
                                         addr=cfg.advertise_addr,
                                         heartbeat_period=hb_period,
                                         prune_timeout=hb_timeout)
            self._clients[self.lifecycler.desc.addr] = self.ingester

        self.generator = self.generator_lifecycler = None
        gen_forward = None
        if cfg.enable_generator and has("metrics-generator"):
            from .generator import MetricsGenerator

            self.generator = MetricsGenerator(self.overrides)
            gen_forward = self.generator.push
            if shared_ring and cfg.target == "metrics-generator":
                # standalone generator joins its own ring so distributors
                # shuffle-shard tenants across the generator fleet
                self.generator_lifecycler = Lifecycler(
                    self.kv, GENERATOR_RING, cfg.instance_id, addr=cfg.advertise_addr
                )

        self.distributor = None
        if has("distributor"):
            # local generator -> in-process tap; shared-KV topology with
            # no local generator -> shuffle-sharded remote generator ring
            gen_ring = (
                Ring(self.kv, GENERATOR_RING)
                if shared_ring and self.generator is None
                else None
            )
            # streaming tap: when the generator AND the ingester share
            # this process, the tap reads coded span columns out of the
            # ingester's ColumnarIngest cache (the write path already
            # decoded them) instead of re-decoding traces
            gen_window = (
                self._generator_window
                if self.generator is not None and self.ingester is not None
                else None
            )
            self.distributor = Distributor(
                self.ring, self.client_for, self.overrides,
                generator_forward=gen_forward, generator_ring=gen_ring,
                generator_window=gen_window,
            )

        self.querier = self.frontend = self.querier_worker = None
        if has("querier") or has("query-frontend"):
            # with a shared KV the ring may hold remote ingesters even when
            # this process hosts none
            ingester_ring = self.ring if (self._clients or shared_ring) else None
            ext = [e.strip() for e in cfg.search_external_endpoints.split(",")
                   if e.strip()]
            self.querier = Querier(
                self.db, ingester_ring, self.client_for,
                external_endpoints=ext,
                external_hedge_after_s=cfg.search_external_hedge_after_s,
            )
            # a standalone query-frontend with remote queriers attached is
            # dispatcher-only (v1/frontend.go); every other shape keeps
            # in-process workers draining the same queue
            n_workers = cfg.frontend_workers
            if cfg.target == "query-frontend" and shared_ring:
                n_workers = 0
            if n_tree > 1:
                from ..util.log import get_logger
                from .proctree import TREE_WORKER_CONCURRENCY

                if n_workers > TREE_WORKER_CONCURRENCY:
                    # said aloud: the tree overrides a configured value
                    get_logger("app").info(
                        "process tree: instance 0 runs fewer in-process "
                        "workers than frontend_workers asks for",
                        frontend_workers=n_workers,
                        running=TREE_WORKER_CONCURRENCY,
                        querier_worker_concurrency=cfg.worker_concurrency)
                n_workers = min(n_workers, TREE_WORKER_CONCURRENCY)
            self.frontend = Frontend(self.querier, n_workers=n_workers,
                                     overrides=self.overrides)
            if self.frontend.result_cache is not None and self.ingester is not None:
                # live-head generation feed: result-cache entries over
                # ranges touching the live window key on it, so every
                # push/cut/flush invalidates them naturally. Without a
                # local ingester those ranges stay uncacheable (the
                # extension prefix never includes the live window).
                self.frontend.result_cache.live_gen = self.ingester.live_generation
            if cfg.target == "querier" and cfg.frontend_addr:
                from .worker import QuerierWorker

                self.querier_worker = QuerierWorker(
                    self.querier,
                    [a.strip() for a in cfg.frontend_addr.split(",") if a.strip()],
                    token=cfg.internal_token,
                    concurrency=cfg.worker_concurrency,
                    worker_id=cfg.instance_id,
                    device=self.device,
                )

        # blocklist-poll sharding (fleet/poller_shard): standalone
        # queriers on a shared ring join the querier ring and each polls
        # only the tenants it owns, reading peers' indexes for the rest
        self.querier_lifecycler = self.poller_shard = None
        if shared_ring and cfg.target == "querier":
            from ..fleet.poller_shard import PollerShard

            self.querier_lifecycler = Lifecycler(
                self.kv, QUERIER_RING, cfg.instance_id,
                heartbeat_period=hb_period, prune_timeout=hb_timeout)
            self.poller_shard = PollerShard(
                Ring(self.kv, QUERIER_RING, heartbeat_timeout=hb_timeout),
                cfg.instance_id)
            self.poller_shard.install(self.db)

        self.tree = None
        if n_tree > 1:
            from .proctree import QuerierTree

            self.tree = QuerierTree(cfg, n_tree, self.frontend, cfg.kv_dir)

        self.compactor = self.compactor_lifecycler = None
        if has("compactor"):
            # compactors own jobs via their OWN ring (the reference's
            # compactor ring, modules/compactor/compactor.go:36-38) -- an
            # ingester-ring membership test would never match a standalone
            # compactor process
            self.compactor_lifecycler = Lifecycler(self.kv, COMPACTOR_RING, cfg.instance_id)
            comp_ring = Ring(self.kv, COMPACTOR_RING)
            self.compactor = Compactor(self.db, comp_ring, cfg.instance_id,
                                       cycle_s=cfg.compaction_cycle_s)
        if (cfg.self_tracing_tenant and self.frontend is not None
                and self.distributor is not None):
            from .selftrace import SelfTracer

            self.frontend.self_tracer = SelfTracer(
                self.distributor.push, tenant=cfg.self_tracing_tenant
            )

        # SLO plane (util/slo): declarative objectives over the metrics
        # this process already collects, evaluated as multi-window burn
        # rates on /status/slo + tempo_slo_burn_rate gauges. Query-
        # serving roles only -- a standalone compactor has no read SLIs.
        self.slo = (build_default_slo(self.frontend, self.generator)
                    if (self.frontend or self.generator) else None)

        from .usagestats import UsageReporter

        self.usage = UsageReporter(self.db.backend, cfg.target)
        self.warmup_report: dict | None = None
        self._started = False
        self.otlp_grpc = None
        self.opencensus = None
        self.jaeger_grpc = None
        self.jaeger_agent = None
        self.kafka = None
        self.remote_writer = None
        self.http_server: ThreadingHTTPServer | None = None
        self._profile_lock = threading.Lock()  # one /debug/profile at a time

    def _generator_window(self, tenant: str, segs: list, push_ts: float) -> None:
        """Streaming generator tap (runs on the distributor's tap
        worker): resolve each segment's coded span columns from the
        tenant instance's ColumnarIngest -- the staging path filled
        that identity-keyed cache before the tap item was enqueued, so
        this is a pure cache read with ZERO extra proto decodes
        (ColumnarIngest.decodes proves it) -- and fold the window."""
        with TEL.stage("generator:window", segments=len(segs)):
            col = self.ingester.instance(tenant).columnar
            cols = []
            for seg in segs:
                feat = col.features_for(seg)
                if feat.spans is not None:
                    cols.append(feat.spans)
            if cols:
                self.generator.push_window(tenant, cols, col.dict, push_ts)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        if self.lifecycler:
            self.lifecycler.start()
        if self.compactor_lifecycler:
            self.compactor_lifecycler.start()
        if self.generator_lifecycler:
            self.generator_lifecycler.start()
        if self.querier_lifecycler:
            self.querier_lifecycler.start()
        if self.ingester:
            self.ingester.start_sweeper()
        if self.compactor:
            self.compactor.start()
        if self.querier_worker:
            self.querier_worker.start()
        self.overrides.start_reloader()  # hot-reload per-tenant limits
        if self.generator is not None and self.cfg.remote_write_url:
            from .remotewrite import RemoteWriter

            self.remote_writer = RemoteWriter(
                self.generator, self.cfg.remote_write_url,
                interval_s=self.cfg.remote_write_interval_s,
            )
            self.remote_writer.start()
        if self.distributor is not None and self.cfg.otlp_grpc_port != 0:
            from .otlp_grpc import OTLPGrpcReceiver

            self.otlp_grpc = OTLPGrpcReceiver(self)
            port = max(0, self.cfg.otlp_grpc_port)  # -1 -> ephemeral
            self.cfg.otlp_grpc_port = self.otlp_grpc.start(
                port, host=self._bind_host())
        if self.distributor is not None and self.cfg.opencensus_grpc_port != 0:
            from .opencensus_grpc import OpenCensusReceiver

            self.opencensus = OpenCensusReceiver(self)
            port = max(0, self.cfg.opencensus_grpc_port)  # -1 -> ephemeral
            self.cfg.opencensus_grpc_port = self.opencensus.start(
                port, host=self._bind_host())
        if self.distributor is not None and self.cfg.jaeger_grpc_port != 0:
            from .jaeger_grpc import JaegerGrpcReceiver

            self.jaeger_grpc = JaegerGrpcReceiver(self)
            port = max(0, self.cfg.jaeger_grpc_port)  # -1 -> ephemeral
            self.cfg.jaeger_grpc_port = self.jaeger_grpc.start(
                port, host=self._bind_host())
        if self.distributor is not None and self.cfg.jaeger_agent_port != 0:
            if self.cfg.multitenancy:
                # UDP datagrams cannot carry X-Scope-OrgID: every push
                # would 401 and silently vanish -- fail the config loudly
                raise ValueError(
                    "jaeger_agent_port requires multitenancy off "
                    "(UDP carries no tenant header)")
            from .jaeger_agent import JaegerAgentReceiver

            self.jaeger_agent = JaegerAgentReceiver(self)
            want = max(0, self.cfg.jaeger_agent_port)
            cport, _bport = self.jaeger_agent.start(
                want, want + 1 if want else 0, host=self._bind_host())
            self.cfg.jaeger_agent_port = cport
        if self.distributor is not None and self.cfg.kafka_brokers:
            from .kafka_receiver import DEFAULT_TOPIC, KafkaReceiver

            if self.cfg.multitenancy and not self.cfg.kafka_tenant:
                # fail at startup, not by silently dropping every message
                raise ValueError(
                    "the kafka receiver needs --distributor.kafka-tenant "
                    "when multitenancy is enabled (messages carry no "
                    "X-Scope-OrgID)"
                )
            self.kafka = KafkaReceiver(
                self, self.cfg.kafka_brokers,
                topic=self.cfg.kafka_topic or DEFAULT_TOPIC,
                tenant=self.cfg.kafka_tenant or DEFAULT_TENANT,
            )
            self.kafka.start()
        if self.slo is not None:
            try:
                slo_interval = float(os.environ.get("TEMPO_SLO_EVAL_S", "")
                                     or 15)
            except ValueError:
                slo_interval = 15.0  # a typo'd env must not abort startup
            self.slo.start(interval_s=slo_interval)
        # always-on attributed sampler (TEMPO_PROFILE_HZ, 0 = strict
        # no-op) + the Go-runtime-equivalent GC/thread/RSS gauges
        from ..util import profiler as _profiler
        from ..util import runtimestats as _runtimestats

        _profiler.PROF.ensure_sampler()
        _runtimestats.install()
        if self.cfg.warmup_shapes:
            # pre-serve AOT warmup: compile the ledger's recorded
            # (op, bucket) corpus (through the persistent compile
            # cache when enabled) before the first query arrives
            from ..util.warmup import run_warmup

            self.warmup_report = run_warmup()
        self.db.enable_polling()
        if self.tree is not None:
            self.tree.start()  # they poll with back-off until the port serves
        self._started = True

    def stop(self) -> None:
        if self.tree is not None:
            self.tree.stop()  # children first: they post results here
        if self.distributor is not None:
            self.distributor.stop()  # drain the async generator tap
        if self.remote_writer is not None:
            self.remote_writer.stop()
        self.overrides.stop()
        if self.otlp_grpc is not None:
            self.otlp_grpc.stop()
        if self.opencensus is not None:
            self.opencensus.stop()
        if self.jaeger_grpc is not None:
            self.jaeger_grpc.stop()
        if self.jaeger_agent is not None:
            self.jaeger_agent.stop()
        if self.kafka is not None:
            self.kafka.stop()
        if self.slo is not None:
            self.slo.stop()
        if self.querier_worker:
            self.querier_worker.stop()
        if self.compactor:
            self.compactor.stop()
        if self.ingester:
            self.ingester.stop()
        if self.frontend:
            self.frontend.stop()
        if self.lifecycler:
            self.lifecycler.leave()
        if self.compactor_lifecycler:
            self.compactor_lifecycler.leave()
        if self.generator_lifecycler:
            self.generator_lifecycler.leave()
        if self.querier_lifecycler:
            self.querier_lifecycler.leave()
        self.db.close()
        if hasattr(self.kv, "close"):
            self.kv.close()  # gossip mode: stop the server + sync loop
        if self.http_server:
            self.http_server.shutdown()

    def ready(self) -> bool:
        if not self._started:
            return False
        if self.tree is not None and not self.tree.ready():
            return False  # a querier has not attached (or died)
        if self.ingester is not None:
            return bool(self.ring.healthy_instances())
        return True

    @staticmethod
    def _warn_orphan_wals(wal_root: str, instance_id: str) -> None:
        """WAL dirs are per --instance.id; a renamed instance would silently
        strand its predecessor's unflushed data, so surface any sibling
        WAL dir that still holds files."""
        from ..util.log import get_logger

        try:
            entries = os.listdir(wal_root)
        except OSError:
            return
        for name in entries:
            p = os.path.join(wal_root, name)
            if name != instance_id and os.path.isdir(p) and os.listdir(p):
                get_logger("app").warning(
                    "orphaned WAL dir %s holds unreplayed files from instance %r; "
                    "restart with --instance.id %s to replay it",
                    p, name, name,
                )

    # ------------------------------------------------------------ tenant
    def tenant_of(self, headers, read: bool = False) -> str:
        if not self.cfg.multitenancy:
            t = headers.get(TENANT_HEADER, "")
            if read and t and t == self.cfg.self_tracing_tenant:
                # READ-only carve-out: the self-tracing tenant stays
                # queryable in single-tenant mode so the dogfood loop
                # (tempo-cli self-trace) works against the plain dev
                # app. Ingest never honors the header here -- a client
                # must not be able to push spoofed spans into the
                # system's own diagnostic tenant.
                return t
            return DEFAULT_TENANT
        t = headers.get(TENANT_HEADER, "")
        if not t:
            raise PushError(401, f"missing {TENANT_HEADER} header")
        return t

    # ------------------------------------------------------------ http
    def _bind_host(self) -> str:
        """Bind policy shared by the HTTP server and every gRPC
        receiver: explicit http_host wins; else a non-loopback advertise
        addr implies peers connect from other hosts (bind all
        interfaces), else stay loopback-only."""
        if self.cfg.http_host:
            return self.cfg.http_host
        adv = self.cfg.advertise_addr
        local = ("127.0.0.1" in adv) or ("localhost" in adv) or not adv
        return "127.0.0.1" if local else "0.0.0.0"

    def serve_http(self, port: int | None = None, background: bool = False):
        handler = _make_handler(self)
        host = self._bind_host()
        self.http_server = ThreadingHTTPServer((host, port or self.cfg.http_port), handler)
        if background:
            t = threading.Thread(target=self.http_server.serve_forever, daemon=True)
            t.start()
            return self.http_server
        self.http_server.serve_forever()


def _make_handler(app: App):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):  # quiet
            pass

        def _send(self, code: int, body: bytes | str, ctype="application/json",
                  headers: dict | None = None):
            if isinstance(body, str):
                body = body.encode()
            with TEL.stage("http:write", bytes=len(body), status=code):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

        @staticmethod
        def _cache_headers() -> dict:
            """X-Tempo-Cache: hit|miss|extend for the query routes --
            how soak's --repeat-zipf and the vulture cached_vs_fresh
            probes classify responses client-side."""
            from .resultcache import LAST_OUTCOME

            outcome = LAST_OUTCOME.get()
            LAST_OUTCOME.set(None)
            return {"X-Tempo-Cache": outcome} if outcome else {}

        def _err(self, code: int, msg: str):
            self._send(code, json.dumps({"error": msg}))

        def _stream_json(self, events, sse: bool) -> None:
            """Write an event iterator as a chunked HTTP/1.1 response:
            SSE `data:` frames or NDJSON lines, one flush per event so
            the client sees each partial the moment its shard lands.
            The first event is pulled BEFORE the headers go out, so
            admission errors (QoS 429) still surface as real statuses."""
            import itertools

            close = getattr(events, "close", None)  # BEFORE chain rebinds
            try:
                first = next(events)
            except StopIteration:
                first = None
            else:
                events = itertools.chain([first], events)
            self.send_response(200)
            self.send_header(
                "Content-Type",
                "text/event-stream" if sse else "application/x-ndjson")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            def chunk(payload: bytes) -> bytes:
                return b"%X\r\n%s\r\n" % (len(payload), payload)

            try:
                if first is not None:
                    for obj in events:
                        data = json.dumps(obj)
                        payload = (f"data: {data}\n\n"
                                   if sse else data + "\n").encode()
                        self.wfile.write(chunk(payload))
                        self.wfile.flush()
                self.wfile.write(b"0\r\n\r\n")
            except (BrokenPipeError, ConnectionResetError):
                # client went away mid-stream: close the generator so it
                # cancels its jobs and releases its QoS charge
                if close is not None:
                    close()
            except Exception:
                # headers are already out: propagating would let do_GET
                # write a SECOND status line into the chunked body. Close
                # the generator (cancels jobs, releases QoS) and end the
                # chunked stream so the client sees clean termination.
                if close is not None:
                    close()
                try:
                    self.wfile.write(b"0\r\n\r\n")
                except OSError:
                    pass

        def _authorized_internal(self) -> bool:
            """Operational + internal endpoints: loopback peers are always
            trusted; remote peers must present the shared token."""
            if self.client_address[0] in ("127.0.0.1", "::1"):
                return True
            tok = app.cfg.internal_token
            return bool(tok) and self.headers.get("X-Tempo-Internal-Token", "") == tok

        # ----------------------------------------------------------- GET
        # The served routes are rooted where they branch off, in an
        # `http:<route>` stage that ends with the reply's last byte: every
        # request in flight is under one, so a device-trace idle gap no
        # `tempo/http:*` annotation covers had nothing to serve.
        def do_GET(self):
            u = urlparse(self.path)
            q = {k: v[0] for k, v in parse_qs(u.query).items()}
            # a tree's status speaks for the deployment unless asked for
            # this instance alone
            whole_tree = app.tree is not None and q.get("scope") != "instance"
            try:
                # operational endpoints never need a tenant (probes/scrapes
                # carry no X-Scope-OrgID)
                if u.path == "/api/echo":
                    return self._send(200, "echo", "text/plain")
                if u.path == "/ready":
                    return self._send(200 if app.ready() else 503, "ready" if app.ready() else "starting", "text/plain")
                if u.path == "/metrics":
                    # OpenMetrics: exemplars on histogram buckets are only
                    # legal in this format (classic text parsers reject
                    # the `# {...}` suffix), and it requires the EOF marker
                    return self._send(
                        200, _metrics_text(app) + "# EOF\n",
                        "application/openmetrics-text; version=1.0.0; charset=utf-8",
                    )
                if u.path == "/status/config":
                    # ?mode=defaults -> the built-in config; ?mode=diff
                    # -> only fields differing from it (the reference's
                    # /status/config?mode= variants)
                    mode = q.get("mode", "")
                    if mode not in ("", "diff", "defaults"):
                        return self._err(
                            400, f"unknown mode {mode!r}; one of diff, defaults")
                    cfg_d = _config_dict(app.cfg)
                    if mode == "defaults":
                        cfg_d = _config_dict(AppConfig())
                    elif mode == "diff":
                        defaults = _config_dict(AppConfig())
                        cfg_d = {k: v for k, v in cfg_d.items()
                                 if v != defaults.get(k)}
                    return self._send(200, json.dumps(cfg_d, indent=2))
                if u.path == "/status/kernels":
                    # kernel telemetry: compile/cache-hit table, staged-
                    # cache contents, routing reasons, slow-query log
                    own = _kernel_status(app)
                    if whole_tree:
                        # the sum over instances, each one's own beside
                        from .proctree import tree_kernel_status

                        own = tree_kernel_status(
                            own, _tree_get(app, "/status/kernels"))
                    return self._send(200, json.dumps(own, indent=2))
                if u.path == "/status/cost":
                    # device cost plane (util/costmodel): per-(op,bucket)
                    # FLOPs/bytes/utilization vs roofline, collective
                    # comm bytes, the HBM ledger, the crossover ledger
                    # and compile-cache state
                    from ..util.costmodel import COST

                    snap = COST.status_snapshot()
                    if whole_tree:
                        # every instance's chip in the HBM list, numbered
                        # by instance (each sees its own as device 0)
                        stats = snap.setdefault("hbm", {}).setdefault(
                            "per_device_memory_stats", [])
                        for info, other in _tree_get(app, "/status/cost"):
                            for d in ((other.get("hbm") or {}).get(
                                    "per_device_memory_stats") or []):
                                stats.append({**d, "id": info["index"],
                                              "instance": info["id"]})
                    return self._send(200, json.dumps(snap, indent=2))
                if u.path == "/status/chaos":
                    # chaos + resilience surface: active fault rules
                    # with call/fire counts, the recent injection log,
                    # circuit-breaker legs, retry-budget + hedge
                    # counters, and the warmup report when --warmup.
                    # shapes ran
                    from ..chaos import plane as chaos_plane
                    from ..util.breaker import breakers_snapshot

                    out = chaos_plane.status()
                    out["breakers"] = breakers_snapshot()
                    out["retries"] = TEL.retry_stats()
                    out["hedging"] = TEL.hedge_stats()
                    if app.warmup_report is not None:
                        out["warmup"] = app.warmup_report
                    return self._send(200, json.dumps(out, indent=2))
                if u.path == "/status/fleet":
                    # the cluster operator's one-stop view: ring members
                    # with heartbeat ages, RF + quorum arithmetic,
                    # replica push-leg breaker health, replication write
                    # outcomes, the poller shard map and per-tenant
                    # queue depths
                    return self._send(
                        200, json.dumps(_fleet_status(app), indent=2))
                if u.path == "/status/slo":
                    # the SLO plane's verdict surface: every objective
                    # with its multi-window burn rates (util/slo),
                    # re-evaluated at request time so the payload is
                    # never staler than the ask
                    if app.slo is None:
                        return self._err(
                            404, f"target {app.cfg.target} serves no "
                                 "query SLOs")
                    return self._send(
                        200, json.dumps(app.slo.evaluate(), indent=2))
                if u.path == "/status/usage-stats":
                    return self._send(200, json.dumps(app.usage.report(app), indent=2))
                if u.path == "/status/profile":
                    # continuous profiling plane (util/profiler):
                    # sampler state + per-component sample counts +
                    # top-stack summaries, lock-contention table,
                    # slow-capture count and the artifact index
                    from ..util.profiler import PROF

                    return self._send(
                        200, json.dumps(PROF.status_snapshot(), indent=2))
                if u.path == "/debug/threads":
                    # every thread's current stack (the role the
                    # reference's pprof goroutine dump plays): first stop
                    # for "what is this process stuck on". Same trust
                    # gate as /internal/*: loopback or shared token
                    # (stacks leak code paths; see _authorized_internal)
                    if not self._authorized_internal():
                        return self._err(403, "forbidden")
                    import sys
                    import traceback as _tb

                    names = {t.ident: t.name for t in threading.enumerate()}
                    parts = []
                    for tid, frame in sys._current_frames().items():
                        parts.append(f"--- thread {names.get(tid, tid)}\n")
                        parts.extend(_tb.format_stack(frame))
                    return self._send(200, "".join(parts), "text/plain")
                if u.path == "/debug/profile":
                    # on-demand burst CPU profile over ?seconds=N
                    # (default 2, capped): the pprof profile endpoint
                    # analog (util/profiler.sample_cpu). Samples
                    # sys._current_frames() across ALL threads at
                    # ?hz= (default 200; a tracing profiler would only
                    # see this handler's thread). ?format=text renders
                    # the hottest stacks; ?format=folded streams the
                    # flamegraph-collapsed table. One at a time:
                    # overlapping scrapes get a 409. Gated like
                    # /internal/*: a repeatable multi-second CPU burn
                    # must not be open to unauthenticated remote peers.
                    if not self._authorized_internal():
                        return self._err(403, "forbidden")
                    from ..util.profiler import PROF

                    fmt = q.get("format", "text")
                    if fmt not in ("text", "folded"):
                        return self._err(
                            400, f"unknown format {fmt!r}; text or folded")
                    try:
                        secs = min(max(float(q.get("seconds", 2.0)), 0.1), 30.0)
                        hz = float(q.get("hz", 200.0))
                    except ValueError:
                        return self._err(400, "seconds/hz must be numbers")
                    if not app._profile_lock.acquire(blocking=False):
                        return self._err(409, "a profile is already running")
                    try:
                        return self._send(200, PROF.sample_cpu(secs, hz, fmt),
                                          "text/plain")
                    finally:
                        app._profile_lock.release()
                if u.path == "/debug/profile/device":
                    # device profile: record jax.profiler trace events
                    # for ?seconds=N while serving continues and publish
                    # the zipped trace directory as an artifact (fetch
                    # via /debug/profile/artifact/<id> or
                    # `tempo-tpu-cli profile device`). Device planes,
                    # XLA runtime events and the tempo/<layer>:<stage>
                    # annotations; ?python=1 adds jax's Python tracer
                    # (frames, at ~14x on pure-Python code and a stop
                    # that freezes every thread for seconds)
                    if not self._authorized_internal():
                        return self._err(403, "forbidden")
                    from ..util.profiler import PROF, ProfilerUnavailable

                    try:
                        secs = min(max(float(q.get("seconds", 2.0)), 0.1), 60.0)
                    except ValueError:
                        return self._err(400, "seconds must be a number")
                    if not app._profile_lock.acquire(blocking=False):
                        return self._err(409, "a profile is already running")
                    try:
                        if whole_tree:
                            aid, summary = _tree_device_profile(app, secs)
                        else:
                            aid, summary = PROF.capture_device_profile(
                                secs, python=q.get("python", "") in ("1", "true"))
                    except ProfilerUnavailable as e:
                        return self._err(503, f"device profiler: {e}")
                    finally:
                        app._profile_lock.release()
                    return self._send(
                        200, json.dumps({"artifact_id": aid, **summary}))
                m = re.fullmatch(r"/debug/profile/artifact/([^/]+)", u.path)
                if m:
                    # download one profile artifact (slow-query folded
                    # snapshots, device trace zips) from the bounded
                    # store -- ids come from the slow-query log,
                    # /status/profile, or the device endpoint
                    if not self._authorized_internal():
                        return self._err(403, "forbidden")
                    from ..util.profiler import PROF

                    data = PROF.artifact_bytes(m.group(1))
                    if data is None:
                        return self._err(404, f"no artifact {m.group(1)!r}")
                    ctype = ("text/plain" if m.group(1).endswith(".folded")
                             else "application/octet-stream")
                    return self._send(200, data, ctype)
                if app.querier is None:
                    return self._err(404, f"target {app.cfg.target} serves no query API")
                tenant = app.tenant_of(self.headers, read=True)
                m = re.fullmatch(r"/api/traces/([0-9a-fA-F]+)", u.path)
                if m:
                    with TEL.stage("http:find"):
                        return self._trace_by_id(tenant, m.group(1), q)
                m = re.fullmatch(r"/jaeger/api/traces/([0-9a-fA-F]+)", u.path)
                if m:  # tempo-query shim: Jaeger UI JSON
                    from ..util.traceid import parse_trace_id
                    from ..wire.jaeger import trace_to_jaeger

                    tr = app.frontend.find_trace_by_id(tenant, parse_trace_id(m.group(1)))
                    if tr is None:
                        return self._err(404, "trace not found")
                    return self._send(200, json.dumps(trace_to_jaeger(tr)))
                if u.path == "/api/search":
                    with TEL.stage("http:search"):
                        return self._search(tenant, q)
                if u.path == "/api/metrics/query_range":
                    with TEL.stage("http:metrics"):
                        return self._metrics_query_range(tenant, q)
                if u.path == "/api/search/tags":
                    tags = app.querier.search_tags(tenant)
                    return self._send(200, json.dumps({"tagNames": tags}))
                m = re.fullmatch(r"/api/search/tag/([^/]+)/values", u.path)
                if m:
                    vals = app.querier.search_tag_values(tenant, m.group(1))
                    return self._send(200, json.dumps({"tagValues": vals}))
                return self._err(404, f"no route {u.path}")
            except PushError as e:
                return self._err(e.status, str(e))
            except TooManyRequests as e:
                return self._err(429, str(e))
            except Exception as e:
                return self._err(500, f"{type(e).__name__}: {e}")

        def _trace_by_id(self, tenant: str, hex_id: str, q: dict):
            tid = parse_trace_id(hex_id)
            start = int(q.get("start", 0))
            end = int(q.get("end", 0))
            tr = app.frontend.find_trace_by_id(tenant, tid, start, end)
            hdrs = self._cache_headers()
            if tr is None:
                return self._err(404, "trace not found")
            with TEL.stage("http:encode", spans=tr.span_count()):
                body = otlp_json.dumps(tr)
            return self._send(200, body, headers=hdrs)

        def _metrics_query_range(self, tenant: str, q: dict):
            """GET /api/metrics/query_range?q=...&start=...&end=...&step=...
            -- TraceQL metrics over the backend (Prometheus-style matrix
            JSON; start/end unix seconds, step a Go duration or
            seconds). The step grid is aligned (metrics_exec
            align_params), so any client polling cadence yields stable
            buckets."""
            from ..db.metrics_exec import (
                align_params,
                parse_metrics_query,
                to_prometheus,
            )
            from ..traceql.ast import ParseError
            from ..traceql.parser import _parse_duration_ns

            query = q.get("q") or q.get("query", "")
            if not query:
                return self._err(400, "missing q parameter")
            try:
                parse_metrics_query(query)
            except ParseError as e:
                return self._err(400, f"invalid TraceQL metrics query: {e}")
            try:
                end = float(q["end"]) if "end" in q else time.time()
                start = float(q["start"]) if "start" in q else end - 3600.0
                if end <= start:
                    raise ValueError("end must be after start")
                sv = q.get("step", "")
                if sv:
                    try:
                        step = float(sv)
                    except ValueError:
                        step = _parse_duration_ns(sv) / 1e9
                    if step <= 0:
                        raise ValueError(f"invalid step {sv!r}")
                else:
                    # default: ~60 points over the range, 1s floor
                    step = max(1.0, round((end - start) / 60.0))
                req = align_params(query, start, end, step)
            except (ValueError, OverflowError) as e:
                return self._err(400, f"bad query_range parameter: {e}")
            try:
                resp = app.frontend.metrics_query_range(tenant, req)
            except ValueError as e:
                # execution-time request errors (e.g. by() cardinality
                # over the accumulator budget) are the caller's to fix
                return self._err(400, f"query_range failed: {e}")
            with TEL.stage("http:encode"):
                body = json.dumps(to_prometheus(resp))
            return self._send(200, body, headers=self._cache_headers())

        def _search(self, tenant: str, q: dict):
            tags = {}
            if "tags" in q:  # logfmt-ish k=v space separated
                for part in q["tags"].split():
                    if "=" in part:
                        k, v = part.split("=", 1)
                        tags[k] = v.strip('"')
            query = q.get("q", "")
            if query:
                # parse + type-check once at the API boundary so a bad
                # query is a 400, not a per-block failure downstream
                from ..traceql.ast import MetricsQuery, ParseError
                from ..traceql.parser import parse as parse_traceql

                try:
                    parsed = parse_traceql(query)
                except ParseError as e:
                    return self._err(400, f"invalid TraceQL: {e}")
                if isinstance(parsed, MetricsQuery):
                    return self._err(
                        400, "metrics queries (rate(), *_over_time()) belong "
                             "on /api/metrics/query_range, not /api/search")
            def dur_ms(name: str) -> int:
                """Go-style duration params ('300ms', '1m30s', '2h') per
                the reference's time.ParseDuration-based API
                (pkg/api ParseSearchRequest); bare numbers keep this
                API's original plain-seconds reading."""
                v = q.get(name, "")
                if not v:
                    return 0
                try:
                    ms = int(float(v) * 1000)
                except ValueError:
                    from ..traceql.parser import _parse_duration_ns

                    ns = _parse_duration_ns(v)
                    if ns <= 0:
                        raise ValueError(f"invalid duration {name}={v!r}")
                    ms = ns // 1_000_000
                if ms <= 0:
                    # this filter API is ms-granularity; silently mapping
                    # '500us' to 0 would DROP the filter (0 = unset)
                    raise ValueError(
                        f"{name}={v!r} is below this API's 1ms granularity")
                return ms

            try:
                req = SearchRequest(
                    tags=tags,
                    query=query,
                    min_duration_ms=dur_ms("minDuration"),
                    max_duration_ms=dur_ms("maxDuration"),
                    start=int(q.get("start", 0)),
                    end=int(q.get("end", 0)),
                    limit=int(q.get("limit", 20)),
                )
            except (ValueError, OverflowError) as e:
                return self._err(400, f"bad search parameter: {e}")
            stream = q.get("stream", "").lower()
            if stream in ("true", "1", "sse"):
                # progressive delivery: newest-first partial result
                # snapshots flush as ingester/backend shards complete
                # (the reference's streaming search direction). SSE when
                # asked (stream=sse or an event-stream Accept header),
                # newline-delimited JSON otherwise; the final event is
                # the exact blocking-response body plus done=true.
                sse = (stream == "sse"
                       or "text/event-stream" in self.headers.get("Accept", ""))
                return self._stream_json(
                    app.frontend.search_stream(tenant, req), sse)
            resp = app.frontend.search(tenant, req)
            with TEL.stage("http:encode", traces=len(resp.traces)):
                body = json.dumps(
                    {
                        "traces": [t.to_dict() for t in resp.traces],
                        "metrics": {
                            "inspectedBytes": str(resp.inspected_bytes),
                            "inspectedSpans": str(resp.inspected_spans),
                        },
                    }
                )
            return self._send(200, body, headers=self._cache_headers())

        # ---------------------------------------------------------- POST
        def do_POST(self):
            u = urlparse(self.path)
            ln = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(ln) if ln else b""
            try:
                if u.path.startswith("/internal/"):
                    if not self._authorized_internal():
                        return self._err(401, "missing or wrong internal token")
                    from ..transport.client import handle_internal
                    from ..transport.frames import CONTENT_TYPE as FRAMES_CT

                    ctype = self.headers.get("Content-Type", "")
                    if u.path == "/internal/jobs/result":
                        # the frontend's side of the wire: a result decoded
                        with TEL.stage("job:decode", bytes=len(body)):
                            payload = json.loads(body or b"{}")
                    else:
                        payload = ({} if ctype.startswith(FRAMES_CT)
                                   else json.loads(body or b"{}"))
                    code, out = handle_internal(
                        app, u.path, payload, raw_body=body, content_type=ctype,
                        accept=self.headers.get("Accept", ""),
                    )
                    if isinstance(out, tuple):  # (bytes, content_type)
                        return self._send(code, out[0], out[1])
                    return self._send(code, json.dumps(out))
                if u.path == "/v1/traces":  # OTLP HTTP ingest
                    if app.distributor is None:
                        return self._err(404, f"target {app.cfg.target} does not ingest")
                    tenant = app.tenant_of(self.headers)
                    ctype = self.headers.get("Content-Type", "")
                    with TEL.stage("http:push", bytes=len(body)):
                        if "json" in ctype:
                            tr = otlp_json.loads(body)
                            app.distributor.push(tenant, tr.resource_spans)
                        else:
                            # proto bodies take the raw fast path (native
                            # scan + splice; 400 if undecodable)
                            app.distributor.push_raw(tenant, body)
                        return self._send(200, "{}")
                if u.path == "/api/traces":  # Jaeger collector thrift ingest
                    if app.distributor is None:
                        return self._err(404, f"target {app.cfg.target} does not ingest")
                    from ..wire import jaeger_thrift

                    tenant = app.tenant_of(self.headers)
                    try:
                        rs = jaeger_thrift.decode_batch(body)
                    except jaeger_thrift.ThriftError as e:
                        return self._err(400, f"bad thrift payload: {e}")
                    app.distributor.push(tenant, [rs])
                    return self._send(202, "")
                if u.path == "/api/v2/spans":  # Zipkin v2 JSON ingest
                    if app.distributor is None:
                        return self._err(404, f"target {app.cfg.target} does not ingest")
                    from ..wire import zipkin

                    tenant = app.tenant_of(self.headers)
                    app.distributor.push(tenant, zipkin.decode_spans(body))
                    return self._send(202, "")
                if u.path == "/flush":
                    if not self._authorized_internal():
                        return self._err(401, "missing or wrong internal token")
                    with TEL.stage("http:flush"):
                        if app.ingester:
                            app.ingester.flush_all()
                        return self._send(204, "")
                if u.path == "/shutdown":
                    if not self._authorized_internal():
                        return self._err(401, "missing or wrong internal token")
                    if app.ingester:
                        app.ingester.flush_all()
                    threading.Thread(target=app.stop, daemon=True).start()
                    return self._send(204, "")
                return self._err(404, f"no route {u.path}")
            except PushError as e:
                return self._err(e.status, str(e))
            except Exception as e:
                return self._err(500, f"{type(e).__name__}: {e}")

    return Handler


def build_default_slo(frontend, generator=None):
    """The serving objectives every query-capable target ships with
    (util/slo): availability over the frontend's per-class outcome
    counters (QoS sheds excluded -- admission refusing work is the
    budget system functioning), p99-under-threshold latency per query
    class from the frontend latency histogram, and live-head freshness
    from the push->device-visible staging-lag histogram. Targets that
    host a metrics-generator additionally carry the push->series-
    visible generator-freshness objective. Thresholds sit on bucket
    edges; TEMPO_SLO_<CLASS>_P99_S env overrides let an operator
    retune without code."""
    from ..util import slo as slomod
    from ..util.kerneltel import TEL

    def _thr(env: str, default: float) -> float:
        try:
            return float(os.environ.get(env, "") or default)
        except ValueError:
            return default

    engine = slomod.SLOEngine()

    if frontend is not None:
        def outcomes_sli():
            # resolve the instrument through TEL at call time:
            # TEL.reset() (tests) swaps the counter object under us
            return slomod.counter_sli(
                TEL.query_outcomes,
                good=lambda l: 'outcome="ok"' in l,
                bad=lambda l: 'outcome="error"' in l)()

        engine.register(slomod.Objective(
            name="read-availability", kind="availability", target=0.999,
            sli=outcomes_sli,
            description="queries served without error across every query "
                        "class (429 QoS sheds excluded)"))

        for op, env, default in (("traces", "TEMPO_SLO_TRACES_P99_S", 1.0),
                                 ("search", "TEMPO_SLO_SEARCH_P99_S", 2.5),
                                 ("search_stream", "TEMPO_SLO_STREAM_P99_S", 5.0),
                                 ("metrics", "TEMPO_SLO_METRICS_P99_S", 10.0)):
            thr = _thr(env, default)
            engine.register(slomod.Objective(
                name=f"latency-{op}", kind="latency", target=0.99,
                sli=slomod.histogram_sli(
                    frontend.query_latency, thr,
                    labels_pred=lambda l, _op=op: f'op="{_op}"' in l),
                description=f"{op} queries completing within {thr:g}s"))

        fresh_thr = _thr("TEMPO_SLO_FRESHNESS_P99_S", 2.5)
        engine.register(slomod.freshness_objective(
            "live-freshness", lambda: TEL.livestage_lag, fresh_thr,
            description=f"pushes device-visible to live search within "
                        f"{fresh_thr:g}s (livestage staging lag)"))

    if generator is not None:
        gen_thr = _thr("TEMPO_SLO_GENERATOR_FRESHNESS_P99_S", 2.5)
        engine.register(slomod.freshness_objective(
            "generator-freshness", lambda: TEL.generator_freshness, gen_thr,
            description=f"pushed spans reflected in generated series "
                        f"within {gen_thr:g}s (streaming tap fold lag)"))
    return engine


def _tree_get(app: App, path: str) -> list[tuple[dict, dict]]:
    """[(instance row, its JSON answer to `path` or {})] for every
    querier child of a tree, asked at once."""
    from concurrent.futures import ThreadPoolExecutor

    from .proctree import fetch

    def one(info: dict):
        if not info["alive"]:
            return info, {}
        try:
            return info, json.loads(fetch(info["port"], path,
                                          app.cfg.internal_token))
        except (OSError, ValueError):
            return info, {}

    rows = app.tree.instances()
    with ThreadPoolExecutor(max_workers=max(1, len(rows))) as ex:
        return list(ex.map(one, rows))


def _tree_device_profile(app: App, seconds: float) -> tuple[str, dict]:
    """One device-trace artifact for a tree: a session in every
    instance over the same wall-clock span (each process can trace only
    the chip it owns), then one zip whose first file holds every chip's
    plane (proctree.merge_xspaces) and, after it, each instance's own
    files as its profiler wrote them."""
    import io
    import zipfile
    from concurrent.futures import ThreadPoolExecutor

    from ..util.profiler import PROF, ProfilerUnavailable
    from .proctree import fetch, merge_xspaces

    def child(info: dict) -> bytes | None:
        try:
            tok = app.cfg.internal_token
            out = json.loads(fetch(
                info["port"], f"/debug/profile/device?seconds={seconds}",
                tok, timeout=seconds + 120))
            return fetch(info["port"],
                         "/debug/profile/artifact/" + out["artifact_id"],
                         tok, timeout=120)
        except (OSError, ValueError, KeyError):
            return None

    rows = [r for r in app.tree.instances() if r["alive"]]
    with ThreadPoolExecutor(max_workers=max(1, len(rows))) as ex:
        futs = [ex.submit(child, r) for r in rows]
        aid0, summary = PROF.capture_device_profile(seconds)
        zips = [PROF.artifact_bytes(aid0)] + [f.result() for f in futs]
    spaces, files = [], []
    for i, data in enumerate(zips):
        if data is None:
            continue
        with zipfile.ZipFile(io.BytesIO(data)) as z:
            for name in z.namelist():
                body = z.read(name)
                files.append((f"instances/{i}/{name}", body))
                if name.endswith(".xplane.pb"):
                    spaces.append(body)
    if not spaces:
        raise ProfilerUnavailable("no instance produced a trace file")
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("tree.xplane.pb", merge_xspaces(spaces))
        for name, body in files:
            z.writestr(name, body)
    aid = PROF.put_artifact("device", buf.getvalue(), suffix=".zip")
    return aid, {**summary, "bytes": buf.tell(), "files": len(files) + 1,
                 "instances": len(spaces)}


def _kernel_status(app: App) -> dict:
    """The /status/kernels payload: everything an operator needs to
    answer "why was that query slow" one layer below HTTP -- per-op
    compile/cache-hit counts and device time, the staged device-column
    cache's contents, engine routing reasons, and the slowest recent
    queries with their self-trace ids."""
    from ..ops.stage import staged_cache_stats
    from ..util.kerneltel import TEL

    out = TEL.snapshot()
    # which device and which codec path this process actually runs on:
    # a run whose numbers do not say is unusable (ROADMAP A0/A3)
    from .. import native
    from ..util import costmodel
    from ..util.linkcost import measured_link_rtt_ms

    out["device"] = {**app.device,
                     "peaks": costmodel.DEVICE_PEAKS.get(
                         app.device["device_kind"], "unknown"),
                     "link_rtt_ms": measured_link_rtt_ms()}
    out["native"] = native.status()
    out["compile_cache"] = costmodel.compile_cache_stats()
    out["staged_cache"] = staged_cache_stats()
    out["staged_cache"]["budget_note"] = (
        "device HBM budget for staged block columns (ops/stage)")
    # the tiered cache plane: Tier A (frontend result cache) + Tier B
    # (host-RAM compressed column-chunk pool under the HBM staged LRU)
    from ..ops import chunkpool

    rc = app.frontend.result_cache if app.frontend is not None else None
    out["caching"] = {
        "result_cache": rc.stats() if rc is not None else {"enabled": False},
        "chunk_pool": chunkpool.stats(),
    }
    return out


# point-in-time gauges, set at scrape (the reference's promauto GaugeFunc)
from ..util.metrics import Gauge as _Gauge  # noqa: E402
from ..util.metrics import escape_label as _esc  # noqa: E402

_JIT_CACHE_GAUGE = _Gauge("tempo_kernel_jit_cache_entries",
                          help="distinct compiled kernel signatures resident")
_BLOCKLIST_GAUGE = _Gauge("tempo_blocklist_length",
                          help="blocks across all tenants in the blocklist")
_WAL_DEPTH_GAUGE = _Gauge("tempo_ingester_wal_bytes",
                          help="bytes buffered in open WAL head blocks")
_QUEUE_DEPTH_GAUGE = _Gauge(
    "tempo_query_queue_depth",
    help="queued query jobs per tenant (the querier-pool autoscaling "
         "SLI: sustained depth means too few queriers for the load)")

# family -> help for the OpenMetrics renderer (families not listed get a
# generated default; TYPE is inferred from the suffix conventions)
_METRIC_HELP = {
    "tempo_distributor_spans_received": "spans accepted by the distributor",
    "tempo_distributor_push_failures": "quorum write failures (data loss)",
    "tempo_frontend_query_duration_seconds": "frontend query latency by op",
    "tempo_kernel_compiles": "XLA program compiles by op and shape bucket",
    "tempo_kernel_cache_hits": "jit-cache hits by op and shape bucket",
    "tempo_kernel_device_seconds": "per-op device wall time",
    "tempo_engine_routing": "engine routing decisions (layer/engine/reason)",
    "tempo_stage_transfer_bytes": "host->device staging upload bytes",
    "tempo_replication_writes_total":
        "replicated write outcomes per trace (quorum/partial/failed)",
    "tempo_query_queue_depth":
        "queued query jobs per tenant (querier-pool autoscaling SLI)",
}


def _metrics_text(app: App) -> str:
    lines = []
    if app.distributor:
        d = app.distributor.stats
        lines += [
            f"tempo_distributor_spans_received_total {d.spans_received}",
            f"tempo_distributor_bytes_received_total {d.bytes_received}",
            f"tempo_distributor_push_failures_total {d.push_failures}",
            f"tempo_distributor_spans_refused_rate_total {d.spans_refused_rate}",
            f"tempo_distributor_traces_refused_size_total {d.traces_refused_size}",
            f"tempo_distributor_gen_tap_dropped_total {d.gen_tap_dropped}",
        ]
        lines += app.distributor.push_latency.text()
    if app.kafka is not None:
        lines += [
            f"tempo_kafka_receiver_messages_total {app.kafka.messages}",
            f"tempo_kafka_receiver_spans_total {app.kafka.spans}",
            f"tempo_kafka_receiver_failures_total {app.kafka.failures}",
        ]
    if app.opencensus is not None:
        lines += [
            f"tempo_opencensus_receiver_requests_total {app.opencensus.requests}",
            f"tempo_opencensus_receiver_spans_total {app.opencensus.spans}",
            f"tempo_opencensus_receiver_failures_total {app.opencensus.failures}",
        ]
    if app.ingester:
        from .ingester import FLUSH_DURATION, FLUSH_FAILURES, WAL_REPLAYS

        lines += [
            f"tempo_ingester_blocks_flushed_total "
            f"{sum(i.blocks_flushed for i in app.ingester.instances.values())}",
            f"tempo_ingester_live_traces "
            f"{sum(len(i.live) for i in app.ingester.instances.values())}",
        ]
        lines += FLUSH_DURATION.text() + FLUSH_FAILURES.text() + WAL_REPLAYS.text()
    if app.querier is not None:
        q = app.querier.stats
        lines += [
            f"tempo_querier_searches_total {q.searches}",
            f"tempo_querier_traces_found_total {q.traces_found}",
            f"tempo_querier_metrics_queries_total {q.metrics_queries}",
            f"tempo_querier_external_searches_total {q.external_searches}",
            f"tempo_querier_external_failures_total {q.external_failures}",
        ]
    if app.compactor:
        lines += [
            f"tempo_compactor_runs_total {app.compactor.stats.runs}",
            f"tempo_compactor_blocks_compacted_total {app.compactor.stats.blocks_compacted}",
            f"tempo_compactor_blocks_retained_total {app.compactor.stats.blocks_retained}",
            f"tempo_compactor_errors_total {len(app.compactor.stats.errors)}",
        ]
        lines += app.compactor.compaction_duration.text()
    # storage-engine + backend-wrapper metrics (poller, cache, hedging)
    lines += app.db.polls.text() + app.db.poll_errors.text() + app.db.poll_duration.text()
    _BLOCKLIST_GAUGE.set(
        sum(len(app.db.blocklist.metas(t)) for t in app.db.blocklist.tenants()))
    lines += _BLOCKLIST_GAUGE.text()
    b = app.db.backend
    while b is not None:
        if hasattr(b, "hits"):
            lines.append(f"tempo_cache_hits_total {b.hits}")
        if hasattr(b, "hedged_requests"):
            lines.append(f"tempo_backend_hedged_requests_total {b.hedged_requests}")
        b = getattr(b, "inner", None)
    if app.frontend:
        lines += app.frontend.query_latency.text()
    if app.querier_worker:
        lines += [
            f"tempo_querier_worker_jobs_executed_total {app.querier_worker.jobs_executed}",
            f"tempo_querier_worker_jobs_failed_total {app.querier_worker.jobs_failed}",
        ]
    if app.frontend:
        lines += [
            f"tempo_frontend_jobs_local_total {app.frontend.stats_jobs_local}",
            f"tempo_frontend_jobs_remote_total {app.frontend.stats_jobs_remote}",
        ]
        # per-tenant queue depth, zeroing tenants that drained since the
        # last scrape so the gauge never freezes on a stale depth
        depths = app.frontend.queue.depths()
        # unlabeled aggregate always exists, so the queue-depth alert
        # has a series to evaluate even on an idle frontend
        _QUEUE_DEPTH_GAUGE.set(sum(depths.values()))
        stale = getattr(app, "_queue_depth_tenants", set()) - set(depths)
        for t in stale:
            _QUEUE_DEPTH_GAUGE.set(0, labels=f'tenant="{_esc(t)}"')
        for t, n in depths.items():
            _QUEUE_DEPTH_GAUGE.set(n, labels=f'tenant="{_esc(t)}"')
        app._queue_depth_tenants = set(depths) | stale
        lines += _QUEUE_DEPTH_GAUGE.text()
    if app.distributor:
        from ..fleet import replication as _replication

        lines += _replication.metrics_lines()
    if app.generator is not None:
        lines.extend(app.generator.metrics_text())
    # kernel telemetry (compiles, cache hits, device time, staging,
    # routing) + point-in-time gauges
    from ..util.kerneltel import TEL
    from ..util.metrics import render_openmetrics

    lines += TEL.metrics_lines()
    _JIT_CACHE_GAUGE.set(TEL.jit_cache_size())
    lines += _JIT_CACHE_GAUGE.text()
    if app.slo is not None:
        # burn-rate + verdict gauges refresh at scrape time: alert
        # rules must never fire on an evaluator that stalled
        try:
            app.slo.evaluate()
        except Exception:
            pass  # scrape keeps the last published gauges
        lines += app.slo.metrics_lines()
    if app.ingester:
        try:
            _WAL_DEPTH_GAUGE.set(sum(
                inst.head.size_bytes()
                for inst in list(app.ingester.instances.values())))
        except Exception:
            pass  # scrape raced a head-block cut; keep the last value
        lines += _WAL_DEPTH_GAUGE.text()
    helps = dict(_METRIC_HELP)
    helps.update(TEL.help_entries())
    if app.slo is not None:
        helps.update(app.slo.help_entries())
    return render_openmetrics(lines, helps=helps)


def _fleet_status(app: App) -> dict:
    """The /status/fleet payload: ring view with heartbeat ages, RF and
    quorum arithmetic, replica-push breaker health, replication write
    outcomes, the blocklist-poll shard map and per-tenant queue depths."""
    import time as _time

    from ..fleet.replication import replication_snapshot
    from ..util.breaker import breakers_snapshot

    now = _time.time()
    members = [{
        "instance_id": d.instance_id,
        "addr": d.addr,
        "state": d.state.value,
        "heartbeat_age_s": round(now - d.heartbeat_ts, 3),
        "healthy": d.healthy(now, app.ring.heartbeat_timeout),
    } for d in app.ring.instances()]
    rf = app.ring.rf
    # mirror ring.ReplicationSet: majority quorum, except RF=2's
    # eventually-consistent minSuccess=1 (see ring/ring.py)
    write_quorum = 1 if rf <= 2 else rf - (rf - 1) // 2
    brs = breakers_snapshot()
    out = {
        "target": app.cfg.target,
        "instance_id": app.cfg.instance_id,
        "ring": {
            "key": INGESTER_RING,
            "replication_factor": rf,
            "write_quorum": write_quorum,
            "heartbeat_timeout_s": app.ring.heartbeat_timeout,
            "members": members,
            "healthy": sum(1 for m in members if m["healthy"]),
        },
        "replication": {
            "writes": replication_snapshot(),
            "push_breakers": {k: v for k, v in brs.items()
                              if k.startswith("ingester-push:")},
            "read_breakers": {k: v for k, v in brs.items()
                              if k.startswith("ingester:")},
        },
    }
    if app.frontend is not None:
        out["queue_depths"] = app.frontend.queue.depths()
    if app.poller_shard is not None:
        out["poller_shard"] = app.poller_shard.status(
            sorted(set(app.db.blocklist.tenants())
                   | set(app.db.poller.last_shard.get("owned", []))
                   | set(app.db.poller.last_shard.get("deferred", []))))
    else:
        out["poller_shard"] = {"instance_id": app.cfg.instance_id,
                               "solo": True, **app.db.poller.last_shard}
    return out


def _config_dict(cfg: AppConfig) -> dict:
    from dataclasses import asdict

    return asdict(cfg)


def load_config_file(path: str, expand_env: bool = False) -> dict:
    """YAML config root. Precedence: YAML supplies the base, explicitly
    set command-line flags override it. Keys mirror AppConfig fields;
    unknown keys are rejected so typos fail loudly like the reference's
    strict YAML. expand_env substitutes ${VAR} / ${VAR:-default}
    references BEFORE parsing (the reference's --config.expand-env,
    cmd/tempo/main.go envsubst) -- the secrets-from-environment pattern
    for credentials in checked-in config files. Names follow the shell
    grammar [A-Za-z_]\\w* (anything else passes through verbatim), and
    `$$` escapes a literal dollar, so a value that legitimately
    contains ${...} is written `$${...}` -- envsubst behavior."""
    import yaml
    from dataclasses import fields as dc_fields

    with open(path) as f:
        text = f.read()
    if expand_env:
        import os as _os
        import re as _re

        def sub(m):
            if m.group(0) == "$$":
                # envsubst escape: $$ -> literal $, so $${FOO} survives
                # expansion as the literal text ${FOO}
                return "$"
            ref = m.group(1)
            name, has_def, default = ref.partition(":-")
            val = _os.environ.get(name)
            if has_def:
                # shell ':-' semantics: default applies when unset OR empty
                return val if val else default
            if val is None:
                # no default and unset: fail at config load with the real
                # cause, not later as a None field deep in startup
                raise ValueError(
                    f"config references ${{{name}}} but it is not set "
                    f"(use ${{{name}:-default}} for an optional value)")
            return val

        # one alternation pass: the $$ alternative consumes its dollars
        # BEFORE the ${...} branch can see them, which is exactly the
        # escape semantics (names outside [A-Za-z_]\w* never match and
        # pass through verbatim)
        text = _re.sub(r"\$\$|\$\{([A-Za-z_]\w*(?::-[^}]*)?)\}", sub, text)
    data = yaml.safe_load(text) or {}
    valid = {f.name for f in dc_fields(AppConfig)}
    unknown = set(data) - valid - {"ingester"}
    if unknown:
        raise ValueError(f"unknown config keys: {sorted(unknown)}")
    if "ingester" in data:
        data["ingester"] = IngesterConfig(**(data["ingester"] or {}))
    return data


def _prepare_tree(cfg: AppConfig, concurrency_given: bool) -> None:
    """--target scalable-single-binary, before anything touches the
    jax backend: settle how many instances the tree has and pin THIS
    process (instance 0) to its chip. On a TPU host an instance needs a
    chip of its own; under JAX_PLATFORMS=cpu instances are plain
    processes and nothing is pinned."""
    from . import proctree

    chips = proctree.visible_chips()
    n = cfg.scalable_instances or chips or 1
    if n < 1:
        raise ValueError("--scalable.instances must be at least 1")
    if not proctree.on_cpu():
        if chips and n > chips:
            raise ValueError(
                f"--scalable.instances {n} but this host shows {chips} "
                "chips: an instance owns exactly one")
        xb = sys.modules.get("jax._src.xla_bridge")
        if xb is not None and getattr(xb, "_backends", None):
            raise ValueError("the jax backend is already up: too late to pin")
        os.environ.update(proctree.pin_env(0, n))
    cfg.scalable_instances = n
    if not concurrency_given:
        cfg.worker_concurrency = proctree.TREE_WORKER_CONCURRENCY


def main(argv=None):
    ap = argparse.ArgumentParser(prog="tempo-tpu")
    # None defaults = "flag not given"; a flag the user set ALWAYS overrides
    # the config file, even when set to the built-in default value
    ap.add_argument("--config.file", dest="config_file", default="")
    ap.add_argument("--config.expand-env", dest="config_expand_env",
                    action="store_true",
                    help="substitute ${VAR} / ${VAR:-default} in the config file")
    ap.add_argument("--target", default=None)
    ap.add_argument("--http.port", dest="port", type=int, default=None)
    ap.add_argument("--storage.path", dest="storage", default=None)
    ap.add_argument("--overrides.path", dest="overrides", default=None)
    ap.add_argument("--multitenancy", action="store_const", const=True, default=None)
    ap.add_argument("--kv.dir", dest="kv_dir", default=None,
                    help="shared ring-KV directory for multi-process topologies")
    ap.add_argument("--memberlist.bind", dest="gossip_bind", default=None,
                    help="gossip bind addr host:port for multi-HOST rings")
    ap.add_argument("--memberlist.join", dest="gossip_seeds", default=None,
                    help="comma-separated gossip seed peers")
    ap.add_argument("--memberlist.advertise", dest="gossip_advertise", default=None,
                    help="gossip addr peers dial (needed for 0.0.0.0 binds)")
    ap.add_argument("--advertise.addr", dest="advertise", default=None,
                    help="address other processes reach this one at (http://host:port)")
    ap.add_argument("--instance.id", dest="instance_id", default=None)
    ap.add_argument("--replication.factor", dest="rf", type=int, default=None)
    ap.add_argument("--internal.token", dest="internal_token", default=None,
                    help="shared secret for /internal/* when bound beyond loopback")
    ap.add_argument("--querier.frontend-address", dest="frontend_addr", default=None,
                    help="frontend addr(s) a standalone querier pulls jobs from")
    ap.add_argument("--distributor.otlp-grpc-port", dest="otlp_grpc_port", type=int,
                    default=None, help="OTLP gRPC receiver port (0=off, -1=ephemeral)")
    ap.add_argument("--distributor.opencensus-grpc-port", dest="opencensus_grpc_port",
                    type=int, default=None,
                    help="OpenCensus gRPC receiver port (0=off, -1=ephemeral)")
    ap.add_argument("--distributor.jaeger-grpc-port", dest="jaeger_grpc_port",
                    type=int, default=None,
                    help="Jaeger gRPC collector port (0=off, -1=ephemeral)")
    ap.add_argument("--distributor.jaeger-agent-port", dest="jaeger_agent_port",
                    type=int, default=None,
                    help="Jaeger agent UDP compact port; binary opens at +1 "
                         "(0=off, -1=ephemeral)")
    ap.add_argument("--self-tracing.tenant", dest="self_tracing_tenant",
                    default=None,
                    help="tenant the app's own query timelines ship into "
                         "('' = off); inspect with tempo-cli self-trace")
    ap.add_argument("--compile-cache.dir", dest="compile_cache_dir", default=None,
                    help="persistent XLA compilation cache directory; "
                         "JAX_COMPILATION_CACHE_DIR wins when set "
                         "(default: <checkout>/.jax_cache)")
    ap.add_argument("--cost-ledger.path", dest="cost_ledger_path", default=None,
                    help="measured-crossover CostLedger artifact (default: "
                         "TEMPO_COST_LEDGER env, else "
                         "<storage.path>/cost_ledger.json)")
    ap.add_argument("--chaos.rules", dest="chaos_rules", default=None,
                    help="fault-injection rules: inline JSON or a rules "
                         "file path (default: TEMPO_CHAOS env, else off)")
    ap.add_argument("--warmup.shapes", dest="warmup_shapes",
                    action="store_const", const=True, default=None,
                    help="AOT-compile the CostLedger's recorded (op, "
                         "shape-bucket) corpus before serving")
    ap.add_argument("--querier.search-external-endpoints", dest="search_external",
                    default=None,
                    help="comma-separated serverless search handler URLs")
    ap.add_argument("--distributor.kafka-brokers", dest="kafka_brokers", default=None,
                    help="Kafka broker host:port for the kafka receiver ('' = off)")
    ap.add_argument("--distributor.kafka-topic", dest="kafka_topic", default=None)
    ap.add_argument("--distributor.kafka-tenant", dest="kafka_tenant", default=None,
                    help="tenant kafka messages ingest into (required with multitenancy)")
    ap.add_argument("--ring.heartbeat-timeout", dest="ring_heartbeat_timeout",
                    type=float, default=None,
                    help="ring liveness window in seconds; lifecyclers "
                         "also prune peers past it (0 = default 60s)")
    ap.add_argument("--rpc.deadline", dest="rpc_deadline", type=float,
                    default=None,
                    help="per-RPC deadline for remote ingester clients")
    ap.add_argument("--querier.worker-concurrency", dest="worker_concurrency",
                    type=int, default=None,
                    help="standalone-querier worker threads pulling "
                         "frontend jobs")
    ap.add_argument("--scalable.instances", dest="scalable_instances",
                    type=int, default=None,
                    help="--target scalable-single-binary: processes in "
                         "the tree, one chip each; instance 0 serves the "
                         "port, the others are queriers pulling its jobs "
                         "(default: the chips this host shows)")
    ap.add_argument("--lifeline.fd", dest="lifeline_fd", type=int, default=None,
                    help=argparse.SUPPRESS)  # services/proctree.spawn_app
    args = ap.parse_args(argv)
    if args.lifeline_fd is not None:
        from .proctree import watch_lifeline

        watch_lifeline(args.lifeline_fd)
    base = (load_config_file(args.config_file, args.config_expand_env)
            if args.config_file else {})
    flag_vals = {
        "target": args.target,
        "http_port": args.port,
        "storage_path": args.storage,
        "overrides_path": args.overrides,
        "multitenancy": args.multitenancy,
        "kv_dir": args.kv_dir,
        "gossip_bind": args.gossip_bind,
        "gossip_seeds": args.gossip_seeds,
        "gossip_advertise": args.gossip_advertise,
        "advertise_addr": args.advertise,
        "instance_id": args.instance_id,
        "replication_factor": args.rf,
        "internal_token": args.internal_token,
        "frontend_addr": args.frontend_addr,
        "otlp_grpc_port": args.otlp_grpc_port,
        "opencensus_grpc_port": args.opencensus_grpc_port,
        "jaeger_grpc_port": args.jaeger_grpc_port,
        "jaeger_agent_port": args.jaeger_agent_port,
        "self_tracing_tenant": args.self_tracing_tenant,
        "compile_cache_dir": args.compile_cache_dir,
        "cost_ledger_path": args.cost_ledger_path,
        "chaos_rules": args.chaos_rules,
        "warmup_shapes": args.warmup_shapes,
        "search_external_endpoints": args.search_external,
        "kafka_brokers": args.kafka_brokers,
        "kafka_topic": args.kafka_topic,
        "kafka_tenant": args.kafka_tenant,
        "ring_heartbeat_timeout": args.ring_heartbeat_timeout,
        "rpc_deadline_s": args.rpc_deadline,
        "worker_concurrency": args.worker_concurrency,
        "scalable_instances": args.scalable_instances,
    }
    base.update({k: v for k, v in flag_vals.items() if v is not None})
    cfg = AppConfig(**base)
    if not cfg.advertise_addr:
        cfg.advertise_addr = f"http://127.0.0.1:{cfg.http_port}"
    if cfg.target == SCALABLE_TARGET:
        try:
            _prepare_tree(cfg, "worker_concurrency" in base)
        except ValueError as e:
            sys.exit(f"tempo-tpu target={cfg.target}: {e}")
    from ..util.costmodel import NoAcceleratorError

    try:
        app = App(cfg)
    except NoAcceleratorError as e:
        sys.exit(f"tempo-tpu target={cfg.target}: {e}")
    app.start()
    dev = app.device
    print(f"tempo-tpu target={cfg.target} listening on :{cfg.http_port} "
          f"device={dev['platform']} kind={dev['device_kind']!r} "
          f"count={dev['count']}", flush=True)
    # SIGTERM is how supervisors stop the process: drain like ^C and
    # exit 0, so the chip is released for the next process (a tree
    # stops its children first: App.stop)
    signal.signal(signal.SIGTERM, lambda *_: threading.Thread(
        target=app.stop, daemon=True).start())
    try:
        app.serve_http()
    except KeyboardInterrupt:
        app.stop()


if __name__ == "__main__":
    main()
