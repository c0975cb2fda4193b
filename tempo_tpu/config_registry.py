"""Single registry of every operator-facing knob: TEMPO_* environment
variables and the `tempo-tpu` server CLI flags.

Before this module the knob surface lived wherever each subsystem read
it -- 45 env vars across 20 files, documented (or not) wherever a PR
happened to touch. The static analyzer's config-contract rules keep
this registry honest from both ends:

  * env-unregistered: code reads a TEMPO_* name missing here;
  * env-dead: a registered name no code reads;
  * env-doc-drift: a registered name absent from README.md/ops docs.

Both dicts are plain literals on purpose: the analyzer consumes them
via ast.literal_eval without importing anything, and the runtime
helpers below give services a typed read path so new knobs have no
excuse to bypass the registry.

KNOBS maps env name -> (type, default, doc) where type is one of
"bool" (unset/1 = on, "0"/"false" = off unless noted), "int", "float",
"str", "path". Defaults are given as the string the reader falls back
to ("" = unset).
"""

from __future__ import annotations

import os

# env name -> (type, default, one-line doc)
KNOBS: dict[str, tuple[str, str, str]] = {
    "TEMPO_AFFINITY": (
        "bool", "1",
        "cache-affinity query placement across device domains (0 = off)"),
    "TEMPO_AFFINITY_STEAL_MS": (
        "float", "25.0",
        "idle-domain work-steal patience before breaking affinity"),
    "TEMPO_BATCH": (
        "bool", "1",
        "admission-window query batching (0/false = per-query launches)"),
    "TEMPO_BATCH_MAX": (
        "int", "16", "max queries fused into one batched launch"),
    "TEMPO_BATCH_MQ_BUDGET": (
        "int", "1073741824",
        "fused-launch HBM intermediate budget in bytes; groups past it "
        "run sequentially"),
    "TEMPO_BATCH_WINDOW_MS": (
        "float", "2.0", "admission window the batcher holds a leader open"),
    "TEMPO_BREAKER_WINDOW_S": (
        "float", "30.0", "circuit-breaker rolling error window"),
    "TEMPO_BREAKER_MIN_VOLUME": (
        "int", "8", "calls in window before the breaker may trip"),
    "TEMPO_BREAKER_ERROR_RATE": (
        "float", "0.5", "error fraction in window that trips the breaker"),
    "TEMPO_BREAKER_OPEN_S": (
        "float", "5.0", "open-state hold before half-open probing"),
    "TEMPO_BREAKER_PROBES": (
        "int", "2", "successful half-open probes required to close"),
    "TEMPO_BREAKER_PROBE_TIMEOUT_S": (
        "float", "30.0", "half-open probe reply deadline"),
    "TEMPO_CHAOS": (
        "str", "",
        "fault-injection rules: inline JSON or a rules file path "
        "('' = chaos off)"),
    "TEMPO_CHUNK_CACHE": (
        "bool", "1",
        "host-RAM compressed column-chunk tier under the HBM staged "
        "cache (0 = evictions discard, misses re-read the backend)"),
    "TEMPO_CHUNK_CACHE_BUDGET": (
        "int", "1073741824",
        "chunk-tier host pool budget in compressed bytes"),
    "TEMPO_CHUNK_CACHE_CODEC": (
        "str", "none",
        "chunk-tier recompression codec: none/lz4/snappy/zstd -- the "
        "default stores raw bytes (a restage must beat the backend "
        "read + decode + assemble it replaces; recompression only "
        "pays where a native codec wheel is installed)"),
    "TEMPO_CHUNK_CACHE_MAX_ENTRY": (
        "int", "268435456",
        "largest single staged column the chunk tier admits (raw "
        "bytes)"),
    "TEMPO_CHUNK_CACHE_MIN_REUSE": (
        "int", "1",
        "stage count a block needs before eviction demotes instead of "
        "discards (bytes x reuse admission)"),
    "TEMPO_COMPACT_CONCURRENCY": (
        "int", "1", "parallel compaction pipeline workers"),
    "TEMPO_COMPACT_MEM_BUDGET": (
        "int", "1073741824",
        "compaction pipeline admission budget in bytes"),
    "TEMPO_COMPACT_PASSTHROUGH": (
        "bool", "1",
        "copy untouched blocks' compressed bytes verbatim during "
        "compaction (0 = always re-encode)"),
    "TEMPO_COSTMODEL": (
        "bool", "1", "per-(op, bucket) device cost capture (0 = off)"),
    "TEMPO_COSTMODEL_MEMORY": (
        "bool", "1",
        "XLA memory-analysis capture alongside FLOPs (0 = off)"),
    "TEMPO_COST_LEDGER": (
        "path", "",
        "measured-crossover CostLedger artifact path ('' = "
        "<storage>/cost_ledger.json)"),
    "TEMPO_CUT_ENGINE": (
        "str", "",
        "pin block-cut engine to 'device' or 'host' ('' = measured "
        "crossover routing)"),
    "TEMPO_FIND_MODE": (
        "str", "",
        "pin trace-by-id lookup to 'host'/'device'/'auto' ('' = auto)"),
    "TEMPO_KERNELTEL_SYNC": (
        "bool", "",
        "1 = device timers block_until_ready (true device time), "
        "0 = dispatch time only ('' = auto by backend)"),
    "TEMPO_LIVE_CROSSOVER_ROWS": (
        "float", "4096",
        "live-search host/device crossover seed in staged rows"),
    "TEMPO_LIVE_ENGINE": (
        "str", "",
        "pin the live-search engine to 'device' or 'host' ('' = "
        "measured routing)"),
    "TEMPO_LIVE_FIND_DEVICE": (
        "bool", "0", "1 = lower live trace-by-id onto staged rows"),
    "TEMPO_LIVE_STAGE": (
        "bool", "1", "live-head HBM staging of pushed spans (0 = off)"),
    "TEMPO_LOCK_PROFILE": (
        "bool", "0", "1 = contended-lock wait profiling on hot locks"),
    "TEMPO_LOG_LEVEL": (
        "str", "INFO", "structured-log level (DEBUG/INFO/WARNING/ERROR)"),
    "TEMPO_MESH_BATCH": (
        "bool", "1",
        "mesh-sharded batched launches on multi-device (0/false = "
        "single-chip fused path)"),
    "TEMPO_PROFILE_DIR": (
        "path", "",
        "flamegraph/slow-query artifact directory ('' = artifacts off)"),
    "TEMPO_PROFILE_HZ": (
        "float", "19.0", "continuous profiler sampling rate (0 = off)"),
    "TEMPO_RESULT_CACHE": (
        "bool", "1",
        "frontend query-result cache ahead of queue admission (0 = "
        "every query executes; byte-identical to a cacheless build)"),
    "TEMPO_RESULT_CACHE_EXTEND": (
        "bool", "1",
        "incremental extension of cached results for moving now-edge "
        "ranges (0 = exact-range hits only)"),
    "TEMPO_RESULT_CACHE_LIVE_WINDOW_S": (
        "float", "30.0",
        "trailing window treated as mutable live head: ranges ending "
        "inside it key on the ingester live generation, and extension "
        "prefixes stop this far behind now"),
    "TEMPO_RESULT_CACHE_MAX_BYTES": (
        "int", "67108864",
        "result-cache LRU budget in serialized-payload bytes"),
    "TEMPO_RESULT_CACHE_TTL_S": (
        "float", "300.0",
        "result-cache entry lifetime; bounds staleness from spans "
        "arriving later than the live window into old ranges"),
    "TEMPO_RETRY_BUDGET": (
        "int", "0",
        "per-query retry budget override (0 = max(4, jobs/4))"),
    "TEMPO_SELFTRACE_QUEUE": (
        "int", "256", "self-trace export queue depth before drops"),
    "TEMPO_SLO_EVAL_S": (
        "float", "15", "SLO engine evaluation interval"),
    "TEMPO_SLO_FRESHNESS_P99_S": (
        "float", "2.5", "live-search write-to-visible freshness SLO p99"),
    "TEMPO_SLO_GENERATOR_FRESHNESS_P99_S": (
        "float", "2.5", "metrics-generator tap-to-series freshness SLO p99"),
    "TEMPO_SLO_TRACES_P99_S": (
        "float", "1.0", "trace-by-id latency SLO p99"),
    "TEMPO_SLO_SEARCH_P99_S": (
        "float", "2.5", "search latency SLO p99"),
    "TEMPO_SLO_STREAM_P99_S": (
        "float", "5.0", "streamed-search latency SLO p99"),
    "TEMPO_SLO_METRICS_P99_S": (
        "float", "10.0", "TraceQL metrics latency SLO p99"),
    "TEMPO_STREAM_MEM_BUDGET": (
        "int", "268435456",
        "cold-streaming pipeline in-flight byte budget"),
    "TEMPO_STREAM_PREFETCH_DEPTH": (
        "int", "2",
        "cold-streaming units fetched ahead of the consumer (0 = serial)"),
    "TEMPO_STREAM_WORKERS": (
        "int", "0",
        "cold-streaming stage pool size (0 = max(4, cpus/2))"),
    "TEMPO_STRUCT_PACK": (
        "bool", "1",
        "hoisted + bit-packed structural collectives (0/false = legacy "
        "full-width gathers)"),
}

# `tempo-tpu` server flags (services/app.py main): flag -> (type, doc).
# Defaults are all None = "not given" -- a set flag always overrides the
# config file, so the effective defaults live with the config schema.
FLAGS: dict[str, tuple[str, str]] = {
    "--config.file": ("path", "YAML/JSON config file"),
    "--config.expand-env": ("bool", "substitute ${VAR} in the config file"),
    "--target": ("str", "module preset (all/scalable-single-binary/"
                        "distributor/querier/...)"),
    "--scalable.instances": ("int", "scalable-single-binary: processes in "
                                    "the tree, one chip each (default: the "
                                    "chips the host shows)"),
    "--http.port": ("int", "HTTP listen port"),
    "--storage.path": ("path", "block storage root"),
    "--overrides.path": ("path", "per-tenant overrides file"),
    "--multitenancy": ("bool", "enforce X-Scope-OrgID"),
    "--kv.dir": ("path", "shared ring-KV dir for multi-process topologies"),
    "--memberlist.bind": ("str", "gossip bind host:port"),
    "--memberlist.join": ("str", "comma-separated gossip seed peers"),
    "--memberlist.advertise": ("str", "gossip addr peers dial"),
    "--advertise.addr": ("str", "address other processes reach this one at"),
    "--instance.id": ("str", "ring instance identity"),
    "--replication.factor": ("int", "ingest replication factor"),
    "--internal.token": ("str", "shared secret for /internal/*"),
    "--querier.frontend-address": ("str", "frontend addr(s) a standalone "
                                          "querier pulls jobs from"),
    "--distributor.otlp-grpc-port": ("int", "OTLP gRPC receiver port"),
    "--distributor.opencensus-grpc-port": ("int", "OpenCensus receiver port"),
    "--distributor.jaeger-grpc-port": ("int", "Jaeger gRPC collector port"),
    "--distributor.jaeger-agent-port": ("int", "Jaeger agent UDP port"),
    "--self-tracing.tenant": ("str", "tenant for the app's own timelines"),
    "--compile-cache.dir": ("path", "persistent XLA compile cache dir"),
    "--cost-ledger.path": ("path", "CostLedger artifact path"),
    "--chaos.rules": ("str", "fault-injection rules (JSON or file)"),
    "--warmup.shapes": ("bool", "AOT-compile the recorded shape corpus"),
    "--querier.search-external-endpoints": ("str", "serverless search URLs"),
    "--distributor.kafka-brokers": ("str", "Kafka broker host:port"),
    "--distributor.kafka-topic": ("str", "Kafka ingest topic"),
    "--distributor.kafka-tenant": ("str", "tenant kafka messages ingest into"),
    "--ring.heartbeat-timeout": ("float", "ring liveness window seconds"),
    "--rpc.deadline": ("float", "per-RPC deadline for remote clients"),
    "--querier.worker-concurrency": ("int", "standalone-querier job threads"),
}


# ------------------------------------------------------- runtime helpers
def get(name: str) -> str:
    """Registered read: raises on unregistered names so new knobs go
    through the registry (the analyzer catches the literal-string
    bypass)."""
    if name not in KNOBS:
        raise KeyError(f"unregistered knob {name!r}: add it to "
                       "tempo_tpu/config_registry.py KNOBS")
    return os.environ.get(name, KNOBS[name][1])


def get_bool(name: str) -> bool:
    return get(name) not in ("", "0", "false")


def get_int(name: str) -> int:
    try:
        return int(float(get(name)))
    except ValueError:
        return int(float(KNOBS[name][1] or 0))


def get_float(name: str) -> float:
    try:
        return float(get(name))
    except ValueError:
        return float(KNOBS[name][1] or 0)
