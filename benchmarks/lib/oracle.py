"""Plain numpy answers over the generated columns: the benchmark's reference.

`BlockOracle` is copied from chip_smoke.py (PR 21) and knows nothing of the
block format, the readers or any kernel. `save_oracle` / `load_oracle` keep
the columns it needs as .npy files beside the cached corpus, so that a run
maps them (np.load mmap) instead of generating 10 M spans again; what can be
derived (trace_sid, sattr.span, end_ns) is derived on load.
"""

from __future__ import annotations

import json
import os

import numpy as np

SAVED = ("span.dur_us", "span.res_idx", "span.start_ms", "span.start_ns",
         "span.id", "span.name_id", "res.service_id", "sattr.key_id",
         "sattr.str_id")


class BlockOracle:
    """Answers for one block; every method is a pass over plain arrays."""

    def __init__(self, cols: dict, strings: list[str], ids, spans_per: int):
        self.code = {s: i for i, s in enumerate(strings)}
        self.strings = strings
        self.ids = ids
        self.spans_per = spans_per
        self.n_traces = ids.shape[0]
        self.c = cols
        self.n_spans = int(cols["span.dur_us"].shape[0])
        self.base_ms = int(cols["span.start_ns"].min()) // 1_000_000
        self.start_s = int(cols["span.start_ns"].min()) // 1_000_000_000
        self.end_s = int(cols["span.end_ns"].max()) // 1_000_000_000 + 1

    def hex_ids(self, sids) -> set[str]:
        return {self.ids[int(s)].tobytes().hex() for s in sids}

    def _traces_of(self, span_mask) -> set[str]:
        return self.hex_ids(np.unique(self.c["span.trace_sid"][span_mask]))

    def service_spans(self, svc: str):
        c = self.c
        return c["res.service_id"][c["span.res_idx"]] == self.code[svc]

    def attr_spans(self, key: str, val: str):
        c = self.c
        hit = ((c["sattr.key_id"] == self.code[key])
               & (c["sattr.str_id"] == self.code[val]))
        mask = np.zeros(self.n_spans, bool)
        mask[c["sattr.span"][hit]] = True
        return mask

    def traces_service(self, svc: str) -> set[str]:
        return self._traces_of(self.service_spans(svc))

    def traces_attr(self, key: str, val: str) -> set[str]:
        return self._traces_of(self.attr_spans(key, val))

    def traces_duration_gt(self, us: int) -> set[str]:
        return self._traces_of(self.c["span.dur_us"] > us)

    def traces_descendant(self, key: str, val: str, us: int) -> set[str]:
        """{ attr } >> { duration > us }: spans are a chain per trace
        (span k's parent is span k-1), so the descendants of a span are
        the later spans of its trace."""
        c = self.c
        pos = np.arange(self.n_spans) % self.spans_per
        lhs = self.attr_spans(key, val)
        first = np.full(self.n_traces, self.spans_per, np.int64)
        np.minimum.at(first, c["span.trace_sid"][lhs], pos[lhs])
        rhs = (c["span.dur_us"] > us) & (pos > first[c["span.trace_sid"]])
        return self._traces_of(rhs)

    def rate_counts(self, svc: str, start_ms: int, step_ms: int, nb: int):
        """Spans of `svc` per step bucket, by the block format's own
        millisecond start column (span.start_ms, relative to the block's
        first span)."""
        m = self.service_spans(svc)
        abs_ms = self.base_ms + self.c["span.start_ms"][m].astype(np.int64)
        b = (abs_ms - start_ms) // step_ms
        b = b[(b >= 0) & (b < nb)]
        return np.bincount(b, minlength=nb)[:nb]

    def trace_spans(self, sid: int) -> set[tuple]:
        c = self.c
        lo, hi = sid * self.spans_per, (sid + 1) * self.spans_per
        return {(c["span.id"][i].tobytes().hex(),
                 self.strings[int(c["span.name_id"][i])],
                 int(c["span.start_ns"][i]), int(c["span.end_ns"][i]))
                for i in range(lo, hi)}


def save_oracle(path: str, cols: dict, strings: list[str], ids,
                spans_per: int, attrs_per_span: int) -> None:
    os.makedirs(path, exist_ok=True)
    for name in SAVED:
        np.save(os.path.join(path, name + ".npy"), cols[name])
    np.save(os.path.join(path, "ids.npy"), ids)
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump({"strings": strings, "spans_per": spans_per,
                   "attrs_per_span": attrs_per_span}, f)


def load_oracle(path: str) -> BlockOracle:
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    cols = {name: np.load(os.path.join(path, name + ".npy"), mmap_mode="r")
            for name in SAVED}
    ids = np.load(os.path.join(path, "ids.npy"))
    n_spans = cols["span.dur_us"].shape[0]
    cols["span.trace_sid"] = (np.arange(n_spans, dtype=np.int32)
                              // np.int32(meta["spans_per"]))
    cols["sattr.span"] = np.repeat(np.arange(n_spans, dtype=np.int32),
                                   meta["attrs_per_span"])
    cols["span.end_ns"] = (cols["span.start_ns"].astype(np.int64)
                           + cols["span.dur_us"].astype(np.int64) * 1_000
                           ).astype(np.uint64)
    return BlockOracle(cols, meta["strings"], ids, meta["spans_per"])


def spans_of_otlp_json(doc: dict) -> set[tuple]:
    """(span id, name, start, end) of every span of an OTLP/JSON trace."""
    out = set()
    for rs in doc.get("resourceSpans", []):
        for ss in rs.get("scopeSpans", []):
            for sp in ss.get("spans", []):
                out.add((sp["spanId"], sp["name"],
                         int(sp["startTimeUnixNano"]),
                         int(sp["endTimeUnixNano"])))
    return out
