"""Roofline share of the scans of searches that span blocks: the bytes the
traced interval's searches had to read, over the chip's HBM bandwidth, over
the device time of the scan modules. Bytes: `opcost.scan_cost` (the shape's
`SCAN` columns) at each block's OWN padded bucket, summed over EVERY block a
search's range overlaps (`filter_roofline` prices one block a search), and
scaled by the window's device share of block decisions
(`fused_device_share`): a block the router sent to the host engine was not
the chip's work. A block counts once a search however many jobs or time
shards touched it: the same work whatever implements it. Bandwidth-bound,
like every scan here."""
from benchmarks.layer_metrics import fused_device_share
from benchmarks.lib import opcost, readers as R, shapeutil as U
from benchmarks.lib.harness import load_plugin


def read(ctx):
    secs = R.family_seconds(ctx, "scan")
    done = R.in_trace(ctx, R.by_role(ctx, "search"))
    share = fused_device_share.read(ctx)
    if not secs or not done or share is None:
        return None
    peak = opcost.peaks_for(ctx["device"]["device_kind"])
    attrs = ctx["config"]["corpus"]["attrs_per_span"]
    need = 0.0
    for r in done:
        cols = getattr(load_plugin("shapes", r["op"]["shape"]), "SCAN", None)
        if cols is None:  # answered without a span-axis scan (a tag search)
            continue
        for b in U.blocks_overlapping(ctx["env"], r["op"]["start"], r["op"]["end"]):
            blk = ctx["manifest"]["blocks"][b]
            need += opcost.scan_cost(cols, blk["n_spans"], blk["n_traces"],
                                     attrs)["bytes"]
    return 100.0 * (need * share / 100.0 / peak["hbm_bytes_per_s"]) / secs
