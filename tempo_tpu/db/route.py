"""Plan + route: what a block job needs decided before a kernel or a
host scan runs. db/search, db/batchexec and db/metrics_exec ask here.

Each input is defined once: the byte estimates, the host-rate EMA, the
temperature of a reader, the shape of a plan. One function a caller
(route_search, route_fused, route_batch, route_metrics) counts its
decision as routing (layer, engine, reason), once a job and layer. Where
two callers' rules differ the difference is one `if` (ROADMAP C3).
"""

from __future__ import annotations

from dataclasses import dataclass

from ..block import schema as S
from ..block.reader import BackendBlock
from ..ops.filter import T_RATTR, T_RES, T_TRACE, _ATTR_VALUE_COL, required_columns
from ..ops.stage import has_staged, is_staged
from ..util.kerneltel import TEL
from ..util.linkcost import link_rtt_ms

# The most staged-column bytes one search job may hold on the device at
# once. Past it a one-block job streams row-group chunks (a dispatch + a
# result transfer a chunk instead of one a job), and a multi-block group
# (fused on one chip, stacked on a mesh) goes back to run block by block.
JOB_STAGE_BUDGET_BYTES = 512 << 20

# The device engine costs ~one link round trip per query (fused select's
# single fetch) regardless of block count; the host engine costs
# bytes/rate with ZERO round trips (cost model shared with the
# generator's reduce: util/linkcost.py). A host-rate EMA updated by
# every cold host-engine block scan completes the estimate.
_HOST_RATE_BPS: float = 1.5e9  # EMA, seeded at DDR-ish single-core scan rate
_HOST_RATE_SEEDED = False  # ledger seed applied (once per process)


def note_host_rate(n_bytes: int, seconds: float) -> None:
    global _HOST_RATE_BPS
    if seconds > 1e-5 and n_bytes > (1 << 20):
        # lossy EMA on the hot host-scan path: racing writers converge
        # on the same steady state and a lock would serialize every scan
        # tempo: ignore[global-mutation-unlocked] intentional lock-free EMA
        _HOST_RATE_BPS = 0.7 * _HOST_RATE_BPS + 0.3 * (n_bytes / seconds)


def seed_host_rate_from_ledger() -> None:
    """Seed the cold-scan host-rate EMA from the CostLedger's measured
    block_scan entry (tempo-tpu-cli calibrate) instead of the DDR-ish
    constant -- the first routing decisions of a fresh process then
    start from THIS box's measured scan rate. Later scans keep updating
    the EMA as before; called once by TempoDB init (idempotent)."""
    global _HOST_RATE_BPS, _HOST_RATE_SEEDED
    if _HOST_RATE_SEEDED:
        return
    # racing initializers write the same ledger value
    # tempo: ignore[global-mutation-unlocked] once-at-init seed
    _HOST_RATE_SEEDED = True
    try:
        from ..util.costledger import KEY_BLOCK_SCAN, ledger

        entry = ledger().get(KEY_BLOCK_SCAN)
        rate = float(entry.get("host_rate_bps", 0.0)) if entry else 0.0
        if rate > 0:
            # tempo: ignore[global-mutation-unlocked] same seed-once write
            _HOST_RATE_BPS = rate
    except Exception:
        pass  # routing falls back to the constant seed


def _host_cheaper(est_bytes: int) -> bool:
    """A host scan of est_bytes against one device round trip. The RTT
    probe's first use inits the device backend: ask after cheap gates."""
    return est_bytes / _HOST_RATE_BPS * 1e3 < link_rtt_ms()


def tres_eligible(blk: BackendBlock, p) -> bool:
    """Res/trace-only condition trees can evaluate over the tres
    membership axis (one row per (trace, resource) pair, builder.py
    build_tres) instead of the span axis: identical trace mask and
    matched-span counts from a ~10x smaller decode."""
    return (blk.pack.has("tres.res") and bool(p.conds)
            and not getattr(p, "has_struct", False)  # struct needs span rows
            and all(c.target in (T_RES, T_RATTR, T_TRACE) for c in p.conds))


def _tres_needed(conds) -> list[str]:
    need = {"tres.res", "tres.nspans", "trace.tres_off"}
    for c in conds:
        if c.target in (T_TRACE, T_RES):
            need.add(c.col)
        elif c.target == T_RATTR:
            need.update({"rattr.res", "rattr.key_id", "rattr.vtype", "res.service_id"})
            if c.col in _ATTR_VALUE_COL:
                need.add(f"rattr.{_ATTR_VALUE_COL[c.col]}")
    return sorted(need)


def host_plan(blk: BackendBlock, p, groups_range) -> tuple[list[str], bool]:
    """(columns the host engine will read, tres-mode flag). tres mode is
    whole-block only -- row-group shards slice the span axis."""
    if groups_range is None and tres_eligible(blk, p):
        return _tres_needed(p.conds), True
    return [n for n in stage_columns(p) if n != "span.trace_sid"], False


def stage_columns(p) -> list[str]:
    """What a device engine stages for the plan, before its top-k's key."""
    return required_columns(p.conds) + list(p.extra_cols)


def job_rows(blk: BackendBlock, groups_range) -> int:
    """Span rows the job covers: the row-group range's, else the block's."""
    span_ax = blk.pack.axes.get(S.AX_SPAN)
    if span_ax is None:
        return 0
    if groups_range is not None:
        return sum(span_ax.offsets[g + 1] - span_ax.offsets[g] for g in groups_range)
    return span_ax.n_rows


def _span_axis_cols(names) -> int:
    return max(1, sum(1 for n in names if n.startswith(("span.", "sattr."))))


def stage_bytes_est(blk: BackendBlock, p, groups_range=None) -> int:
    """What the job's staged columns take on the device: int32 span-axis
    columns of its rows (the span axis dwarfs the others)."""
    return job_rows(blk, groups_range) * 4 * _span_axis_cols(stage_columns(p))


# tres rows ~= resources-per-trace * traces: 3 int32 columns is honest
_TRES_SCAN_BYTES_PER_TRACE = 4 * 12


def _tres_bytes(blk: BackendBlock) -> int:
    return blk.meta.total_traces * _TRES_SCAN_BYTES_PER_TRACE


def _host_cached(blk: BackendBlock, cols) -> bool:
    """Every column a host scan would read sits in the array cache."""
    return all(blk.pack.has_cached_array(n) for n in cols if blk.pack.has(n))


def scan_bytes_est(blk: BackendBlock, p, groups_range=None) -> int:
    """What a host scan of a one-block job (search_block) would have to
    read: the tres axis for a res/trace-only tree, nothing when its
    columns sit in the array cache (they scan at memory speed), else the
    span-axis columns."""
    cols, tres = host_plan(blk, p, groups_range)
    if tres:
        return _tres_bytes(blk)
    if _host_cached(blk, cols):
        return 0
    return stage_bytes_est(blk, p, groups_range)


# What the numpy engine sustains over a whole block whose columns sit in
# the host array cache, in the bytes fused_host_ms counts. One rate for
# every plan, set between what FOUR threads scanning at once (a served
# process has that many search clients in one interpreter) sustained on
# the chip machine's host over 1.29 M-span blocks: 2.75e9 and 3.76e9 B/s
# for span-axis scans (an attribute equality, a duration bound), 0.57e9
# for the tres axis of a tag search, whose cost is Python, not bytes
# (alone: 2.98e9, 4.09e9, 3.42e9; PERF.md section 6, PR 34).
_HOST_CACHED_RATE_BPS: float = 2.0e9


def fused_host_ms(blk: BackendBlock, p) -> float:
    """What a host scan of one whole block of a fused group is estimated
    to take: the bytes the host engine reads (the tres axis for a
    res/trace-only tree, else its span-axis columns: no span.trace_sid)
    over the cold-scan EMA, or over the memory-speed rate when every
    column sits in the array cache. A cached block is cheap, not free:
    priced at nothing, a hot working set left the device for good once
    one host scan had filled the cache (ROADMAP C3 ii)."""
    cols, tres = host_plan(blk, p, None)
    n_bytes = (_tres_bytes(blk) if tres
               else job_rows(blk, None) * 4 * _span_axis_cols(cols))
    rate = _HOST_CACHED_RATE_BPS if _host_cached(blk, cols) else _HOST_RATE_BPS
    return n_bytes / rate * 1e3


def _kept_hot(blk: BackendBlock) -> bool:
    """search_block's and metrics' test: TempoDB.open_block pinned this
    reader, or it has had columns staged -- any, resident or evicted."""
    return getattr(blk, "device_pinned", False) or has_staged(blk)


def _worth_staging(blk: BackendBlock, stage_cols, groups_range) -> tuple[bool, bool]:
    """The fused engine's and the batch window's test -> (hot, staged
    hit), stricter than _kept_hot (C3): THIS request's columns are
    resident, or the job is a row-group shard of a pinned reader, or the
    block is searched for at least the promote_touches-th time."""
    staged_hit = is_staged(blk, stage_cols, groups_range)
    return (staged_hit
            or (groups_range is not None and getattr(blk, "device_pinned", False))
            # 2: readers TempoDB.open_block did not stamp with its config's
            or getattr(blk, "search_touches", 0) + 1
            >= getattr(blk, "promote_touches", 2)), staged_hit


@dataclass(frozen=True)
class Route:
    """engine: "device" | "stream" (device, over row-group chunks; counted
    as "device") | "host" | "exact" (metrics) | "fallback" (a fast path
    refuses the job). lowered: the program of a job the window accepts."""

    engine: str
    reason: str
    lowered: object = None


def route_search(blk: BackendBlock, p, groups_range=None, mode: str = "auto") -> Route:
    """One block or row-group shard (search_block). mode 'device' |
    'host' forces the engine; 'auto' takes the device for readers kept
    hot, unless a host scan is estimated cheaper than one link round
    trip, and the host for cold one-shot readers, where column upload +
    a dispatch round trip would dominate a single scan."""
    if mode != "auto":
        engine, reason = ("device" if mode == "device" else "host"), "forced"
    elif not _kept_hot(blk):
        engine, reason = "host", "cold_block"
    elif _host_cheaper(scan_bytes_est(blk, p, groups_range)):
        engine, reason = "host", "host_scan_cheaper"
    else:
        engine, reason = "device", "hot_block"
    TEL.record_routing("search_block", engine, reason)
    if engine == "device" and stage_bytes_est(blk, p, groups_range) > JOB_STAGE_BUDGET_BYTES:
        engine = "stream"  # ops/stream counts its own ("stream", "device", "chunked")
    return Route(engine, reason)


def route_fused(live: list[tuple[BackendBlock, object]]) -> list[Route] | None:
    """Whole blocks with their plans (search_blocks_fused): a Route a
    block, or None when the device blocks' staged footprint exceeds the
    budget and the caller must search block by block. Whole query first:
    if scanning every block on host is estimated cheaper than ONE device
    round trip, promotion is a loss however hot the blocks are. Every
    block counts a touch."""
    prefer_host = sum(fused_host_ms(blk, p) for blk, p in live) < link_rtt_ms()
    routes: list[Route] = []
    est = 0
    for blk, p in live:
        hot, staged_hit = _worth_staging(
            blk, stage_columns(p) + ["trace@gkey_s"], None)
        blk.search_touches = getattr(blk, "search_touches", 0) + 1
        if prefer_host:
            routes.append(Route("host", "host_scan_cheaper"))
        elif hot:
            est += stage_bytes_est(blk, p)
            routes.append(Route("device", "staged_hit" if staged_hit else "promoted"))
        else:
            routes.append(Route("host", "cold_block"))
    if est > JOB_STAGE_BUDGET_BYTES:
        # the per-block searches the caller falls back to count their
        # own decisions: counting these too would double-count
        TEL.record_routing("search_fused", "fallback", "pre_io_budget",
                           n=sum(r.engine == "device" for r in routes))
        return None
    for r in routes:
        TEL.record_routing("search_fused", r.engine, r.reason)
    return routes


def route_batch(blk: BackendBlock, p, groups_range=None) -> Route:
    """The batch window's probe (db/batchexec): the lowered program, or
    ("fallback", why). The plan must lower to a predicate program
    (ops/multiquery); tres-eligible plans keep the cheaper host
    membership scan (a shard's too, though search_block scans a shard on
    the span axis: C3); stream-sized blocks keep the chunked path (sized
    by the whole block, whatever the shard: C3); the block must be worth
    staging. An accepted job counts a touch.

    The sequential engine's per-query host_scan_cheaper estimate is
    deliberately NOT mirrored: it weighs one host scan against one
    device round trip, but under the batcher the round trip amortizes
    over the window (RTT/occupancy), which is the point of the
    subsystem -- a lone query on a warm block pays at most one RTT over
    the host estimate, bounded by the admission window policy."""
    from ..ops.multiquery import lower_plan

    lowered = lower_plan(p)
    if lowered is None:
        reason = "ineligible_plan"
    elif tres_eligible(blk, p):
        reason = "tres_host"
    elif stage_bytes_est(blk, p) > JOB_STAGE_BUDGET_BYTES:
        reason = "stream_scan"
    elif not _worth_staging(blk, stage_columns(p) + ["trace.start_ms"], groups_range)[0]:
        reason = "cold_block"
    else:
        blk.search_touches = getattr(blk, "search_touches", 0) + 1
        return Route("device", "batchable", lowered)
    TEL.record_routing("search_batch", "fallback", reason)
    return Route("fallback", reason)


def route_metrics_exact(mode: str, p, by_ok: bool, value_ok: bool) -> Route | None:
    """The exact span-by-span engine and why; None: columnar engines answer."""
    if mode == "exact":
        reason = "forced"
    elif p.needs_verify:
        reason = "lossy_plan"
    elif not by_ok:
        reason = "unplannable_by"
    elif not value_ok:
        reason = "unplannable_value"
    else:
        return None
    TEL.record_routing("metrics", "exact", reason)
    return Route("exact", reason)


def route_metrics(blk: BackendBlock, mode: str, i32_ok: bool) -> Route:
    """The columnar metrics engine of one block. Not i32_ok: step or
    origin past the device kernel's int32 milliseconds (~24.8 days), and
    the int64 host engine runs whatever the mode -- identical results."""
    if i32_ok and (mode == "device" or (mode == "auto" and _kept_hot(blk))):
        route = Route("device", "forced" if mode == "device" else "hot_block")
    else:
        route = Route("host", "forced" if mode == "host"
                      else ("cold_block" if i32_ok else "i32_range"))
    TEL.record_routing("metrics", route.engine, route.reason)
    return route
