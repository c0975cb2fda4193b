"""Bytes and operations a kernel needs, from shapes alone, and the peak each
share is taken against. Kept with the benchmark so that no PR to the program
can change what "the least the chip could take" means.

Every scan kernel of this system is bandwidth-bound: it reads each staged
int32 column once and does about one compare-and-combine per element, i.e.
~0.25 op per byte against a chip that offers ~240 op per byte of HBM traffic
(197e12 / 819e9). So a scan's roofline time is bytes / HBM bandwidth. Rows
are taken at the padded bucket the program launches (the next power of two),
because that is what the kernel has to read as it is launched today.

Which staged columns a request shape's scan reads is the shape's own
knowledge: `benchmarks/shapes/<name>.py` declares `SCAN = {"S": [...], "A":
[...], "T": [...]}` (span, span-attribute and trace axis), and a shape that
launches no scan declares none.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
INT32 = 4


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       "benchmarks/lib/peaks.json: add it with its source")
    return table[device_kind]


def bucket(n: int) -> int:
    """The next power of two: the padded row count a program launches at."""
    b = 1
    while b < n:
        b <<= 1
    return b


def scan_cost(cols: dict, n_spans: int, n_traces: int, attrs_per_span: int) -> dict:
    """filter / timeseries / multiquery over one block for one query that
    reads the staged columns `cols` (a shape's SCAN)."""
    rows = {"S": bucket(n_spans), "A": bucket(n_spans * attrs_per_span),
            "T": bucket(n_traces)}
    elements = sum(len(cols.get(ax, ())) * rows[ax] for ax in rows)
    out_elems = rows["T"]  # one verdict (or count) per trace
    return {"bytes": (elements + out_elems) * INT32, "ops": elements,
            "bound": "bandwidth"}


def select_cost(n_traces: int, k: int) -> dict:
    """Top-k over the per-trace mask and key."""
    t = bucket(n_traces)
    return {"bytes": (2 * t + 2 * k) * INT32, "ops": 2 * t, "bound": "bandwidth"}


def timeseries_cost(cols: dict, n_spans: int, n_traces: int, n_buckets: int) -> dict:
    """A scan that also folds each span into one of n_buckets time steps."""
    c = scan_cost(cols, n_spans, n_traces, 0)
    return {"bytes": c["bytes"] + n_buckets * INT32, "ops": c["ops"] + bucket(n_spans),
            "bound": "bandwidth"}


def mesh_find_cost(n_traces_per_block: int, n_blocks: int, queries: int) -> dict:
    """Bisection over each block's sorted 16-byte ids: log2(n) dependent
    16-byte reads per query and block. Latency-bound, not a roofline case:
    the share is reported against bandwidth only to show how far off it is."""
    steps = bucket(n_traces_per_block).bit_length()
    reads = queries * n_blocks * steps
    return {"bytes": reads * 16, "ops": reads * 4, "bound": "latency"}
