"""Roofline share of the scan kernels: the bytes the traced interval's
searches had to read at their padded bucket (benchmarks/lib/opcost.py, from
shapes), over the chip's HBM bandwidth, over the device time of the scan
modules. Bandwidth-bound: a scan does a compare per 4-byte element. Until
kernels are named in the trace, `filter` stands for the whole family of
jitted `run` programs (filter, multiquery, timeseries, live_filter)."""
from benchmarks.lib import opcost, readers as R
from benchmarks.lib.harness import load_plugin


def read(ctx):
    secs = R.family_seconds(ctx, "scan")
    done = R.in_trace(ctx, R.by_role(ctx, "search") + R.by_role(ctx, "search_beside"))
    if not secs or not done:
        return None
    peak = opcost.peaks_for(ctx["device"]["device_kind"])
    need = 0.0
    for r in done:
        cols = getattr(load_plugin("shapes", r["op"]["shape"]), "SCAN", None)
        if cols is None:  # answered without a scan (a tag search)
            continue
        b = ctx["manifest"]["blocks"][r["op"]["block"]]
        need += opcost.scan_cost(cols, b["n_spans"], b["n_traces"],
                                 ctx["config"]["corpus"]["attrs_per_span"])["bytes"]
    return 100.0 * (need / peak["hbm_bytes_per_s"]) / secs
