"""OTLP push bodies at memcpy speed, and their expected read-back.

One template request (traces x spans, one resource per trace, a chain of
spans, a `smoke.bucket` tag: chip_smoke.py's push corpus) is encoded ONCE by
the program's own OTLP encoder with sentinel ids, times and bucket digits;
their byte offsets are found by search. Every request of a run is the
template with trace ids and span ids from (--seed, request index), times from
the clock at send ("spans dated now") and bucket numbers from the request
index patched in with numpy. Python's encoder (a few MB/s under one GIL)
would otherwise be what the write cell measures.
"""

from __future__ import annotations

import random
import threading
import time

import numpy as np

BUCKET_DIGITS = 6


def _find_all(body: bytes, needle: bytes) -> list[int]:
    out, at = [], body.find(needle)
    while at >= 0:
        out.append(at)
        at = body.find(needle, at + 1)
    return out


class PushTemplate:
    def __init__(self, seed: int, traces: int, spans: int,
                 traces_per_bucket: int):
        from tempo_tpu.wire.model import (Resource, ResourceSpans, Scope,
                                          ScopeSpans, Span, Trace)
        from tempo_tpu.wire.otlp_pb import encode_trace

        self.seed, self.T, self.S = seed, traces, spans
        self.traces_per_bucket = traces_per_bucket
        self.buckets_per_req = -(-traces // traces_per_bucket)
        rnd = random.Random("push-template")
        tids = [rnd.getrandbits(128).to_bytes(16, "big") for _ in range(traces)]
        sids = [rnd.getrandbits(64).to_bytes(8, "big")
                for _ in range(traces * spans)]
        # sentinel times: distinct 8-byte little-endian values (fixed64)
        t_start = [0x7A5A000000000000 + 2 * n for n in range(traces * spans)]
        t_end = [v + 1 for v in t_start]
        buckets = [f"b-{900000 + t:0{BUCKET_DIGITS}d}" for t in range(traces)]
        req = Trace()
        for t in range(traces):
            ss = ScopeSpans(scope=Scope(name="benchmark", version="1"))
            prev = b""
            for k in range(spans):
                n = t * spans + k
                ss.spans.append(Span(
                    trace_id=tids[t], span_id=sids[n], parent_span_id=prev,
                    name=f"smoke-op-{k:02d}", kind=2 if k == 0 else 1,
                    start_unix_nano=t_start[n], end_unix_nano=t_end[n],
                    attrs={"smoke.bucket": buckets[t], "smoke.seq": k}))
                prev = sids[n]
            req.resource_spans.append(ResourceSpans(
                resource=Resource(attrs={"service.name": f"smoke-svc-{t % 8}"}),
                scope_spans=[ss]))
        body = encode_trace(req)
        self.template = np.frombuffer(body, np.uint8).copy()
        self.nbytes = len(body)

        def offsets(needles, per):
            rows = [_find_all(body, nd) for nd in needles]
            if any(len(r) != per for r in rows):
                raise RuntimeError("push template: a sentinel was found "
                                   f"{sorted({len(r) for r in rows})} times, "
                                   f"expected {per}")
            return np.asarray(rows, np.int64)

        self.tid_pos = offsets(tids, spans)                      # (T, S)
        self.bucket_pos = offsets([b.encode() for b in buckets], spans) + 2
        self.start_pos = offsets([v.to_bytes(8, "little") for v in t_start], 1)[:, 0]
        self.end_pos = offsets([v.to_bytes(8, "little") for v in t_end], 1)[:, 0]
        occ_pos, occ_idx = [], []
        for n, sid in enumerate(sids):
            found = _find_all(body, sid)
            want = 1 if n % spans == spans - 1 else 2  # own id + child's parent
            if len(found) != want:
                raise RuntimeError("push template: span id sentinel found "
                                   f"{len(found)} times, expected {want}")
            occ_pos += found
            occ_idx += [n] * len(found)
        self.sid_pos = np.asarray(occ_pos, np.int64)
        self.sid_idx = np.asarray(occ_idx, np.int64)
        # relative times, the same for every request: trace t starts within
        # the first second, span k 1 ms after span k-1, lasts 1-400 ms
        trng = np.random.default_rng([seed, 23])
        t0 = trng.integers(0, 1_000_000_000, size=(traces, 1))
        self.rel_start = (t0 + np.arange(spans)[None, :] * 1_000_000).reshape(-1)
        self.rel_end = self.rel_start + trng.integers(
            1_000_000, 400_000_000, size=traces * spans)
        self._b8, self._b16 = np.arange(8), np.arange(16)

    def ids(self, index: int):
        rng = np.random.default_rng([self.seed, 22, index])
        return (rng.integers(0, 256, size=(self.T, 16), dtype=np.uint8),
                rng.integers(0, 256, size=(self.T * self.S, 8), dtype=np.uint8))

    def bucket_of(self, index: int, trace: int) -> str:
        n = index * self.buckets_per_req + trace // self.traces_per_bucket
        return f"b-{n % 10 ** BUCKET_DIGITS:0{BUCKET_DIGITS}d}"

    def body(self, index: int, base_ns: int) -> bytes:
        tids, sids = self.ids(index)
        buf = self.template.copy()
        buf[self.tid_pos[:, :, None] + self._b16] = tids[:, None, :]
        buf[self.sid_pos[:, None] + self._b8] = sids[self.sid_idx]
        for pos, rel in ((self.start_pos, self.rel_start),
                         (self.end_pos, self.rel_end)):
            vals = (base_ns + rel).astype("<u8").view(np.uint8).reshape(-1, 8)
            buf[pos[:, None] + self._b8] = vals
        for t in range(self.T):
            digits = np.frombuffer(self.bucket_of(index, t)[2:].encode(), np.uint8)
            buf[self.bucket_pos[t][:, None] + np.arange(BUCKET_DIGITS)] = digits
        return buf.tobytes()

    def trace_id(self, index: int, trace: int) -> str:
        return self.ids(index)[0][trace].tobytes().hex()

    def expected_spans(self, index: int, trace: int, base_ns: int) -> set[tuple]:
        _, sids = self.ids(index)
        out = set()
        for k in range(self.S):
            n = trace * self.S + k
            out.add((sids[n].tobytes().hex(), f"smoke-op-{k:02d}",
                     int(base_ns + self.rel_start[n]),
                     int(base_ns + self.rel_end[n])))
        return out

    def bucket_members(self, index: int, bucket: str) -> set[str]:
        tids, _ = self.ids(index)
        return {tids[t].tobytes().hex() for t in range(self.T)
                if self.bucket_of(index, t) == bucket}


class PushLog:
    """What the server acknowledged, and when: the read-back streams draw
    from it and the durability check reads it after the kill."""

    def __init__(self):
        self._lock = threading.Lock()
        self.acked: list[tuple[int, int, float]] = []  # index, base_ns, t_ack

    def add(self, index: int, base_ns: int) -> None:
        with self._lock:
            self.acked.append((index, base_ns, time.monotonic()))

    def older_than(self, age_s: float) -> list[tuple[int, int, float]]:
        cut = time.monotonic() - age_s
        with self._lock:
            n = len(self.acked)
            while n and self.acked[n - 1][2] > cut:
                n -= 1
            return self.acked[:n]
