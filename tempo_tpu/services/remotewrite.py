"""Prometheus remote-write for the metrics-generator registry.

Reference: the generator's registry ships series to a remote-write
endpoint (modules/generator/registry + prometheus remote_write). The
wire is `snappy(protobuf WriteRequest)` POSTed with the prometheus
remote-write headers. Both layers are hand-rolled here:

- WriteRequest proto (prompb): repeated TimeSeries{labels{name,value},
  samples{value,timestamp_ms}} -- encoded with the same pbwire helpers
  the OTLP codec uses.
- snappy framing: the block format's header + ALL-LITERAL chunks, which
  every spec-compliant decoder accepts (compression level is a quality
  knob, not a validity requirement; python has no snappy module baked
  in, and metrics bodies are small).

Series come from the generator's exposition text, so every processor
(span-metrics, service-graphs) ships without knowing about remote-write.
"""

from __future__ import annotations

import re
import struct
import threading
import time
import urllib.request

from ..wire import pbwire as w

_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def snappy_block_encode(data: bytes) -> bytes:
    """Valid snappy block stream of all-literal chunks (max literal tag
    length 2^32-1; we emit <=65536-byte literals with 2-byte lengths)."""
    out = bytearray()
    w.write_varint(out, len(data))  # uncompressed length header
    pos = 0
    while pos < len(data):
        chunk = data[pos : pos + 65536]
        n = len(chunk) - 1
        # literal tag: 61 in the length field = 2-byte little-endian len
        out.append((61 << 2) | 0)
        out += n.to_bytes(2, "little")
        out += chunk
        pos += len(chunk)
    return bytes(out)


def encode_write_request(series: list[tuple[dict, float, int]]) -> bytes:
    """series: (labels incl __name__, value, timestamp_ms) -> WriteRequest."""
    req = bytearray()
    for labels, value, ts_ms in series:
        ts = bytearray()
        for name in sorted(labels):  # prometheus requires sorted label names
            lab = bytearray()
            w.write_string_field(lab, 1, name)
            w.write_string_field(lab, 2, str(labels[name]))
            w.write_message_field(ts, 1, bytes(lab))
        sample = bytearray()
        # explicit encoding: pbwire's field helpers elide proto3 zero
        # defaults, but a remote-write sample of 0 is a real observation
        sample.append((1 << 3) | 1)  # value: fixed64
        sample += struct.pack("<d", float(value))
        sample.append((2 << 3) | 0)  # timestamp: varint
        w.write_varint(sample, int(ts_ms))
        w.write_message_field(ts, 2, bytes(sample))
        w.write_message_field(req, 1, bytes(ts))
    return bytes(req)


def _split_series(line: str) -> tuple[str, str, str] | None:
    """(name, labelstr, rest-after-labels); quote-aware, so label values
    containing braces, spaces or ' # ' never confuse the split."""
    line = line.strip()
    if not line or line.startswith("#"):
        return None
    brace = line.find("{")
    sp = line.find(" ")
    if brace < 0 or (0 <= sp < brace):  # no label set
        if sp < 0:
            return None
        return line[:sp], "", line[sp:]
    i, in_quote, esc = brace + 1, False, False
    while i < len(line):
        c = line[i]
        if esc:
            esc = False
        elif c == "\\":
            esc = True
        elif c == '"':
            in_quote = not in_quote
        elif c == "}" and not in_quote:
            return line[:brace], line[brace + 1 : i], line[i + 1 :]
        i += 1
    return None


def parse_exposition(lines: list[str]) -> list[tuple[dict, float]]:
    """Prometheus text lines -> (labels incl __name__, value). Exemplar
    suffixes (` # {...} v`) after the sample value are ignored."""
    out = []
    for line in lines:
        parts = _split_series(line)
        if parts is None:
            continue
        name, labelstr, rest = parts
        toks = rest.split()
        if not toks:
            continue
        labels = {"__name__": name}
        for lm in _LABEL_RE.finditer(labelstr):
            labels[lm.group(1)] = lm.group(2).replace('\\"', '"')
        try:
            out.append((labels, float(toks[0])))
        except ValueError:
            continue
    return out


class RemoteWriter:
    """Periodic shipper: generator exposition -> remote-write pushes."""

    def __init__(self, generator, url: str, tenant_header: str = "",
                 interval_s: float = 15.0, timeout_s: float = 10.0):
        self.generator = generator
        self.url = url
        self.tenant_header = tenant_header
        self.interval_s = interval_s
        self.timeout_s = timeout_s
        self.pushes = 0
        self.failures = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def push_once(self) -> bool:
        from ..util.kerneltel import TEL

        # shipping rides the generator stage histogram so /status/
        # kernels shows the full pipeline: fold stages + export
        with TEL.stage("generator:remote_write"):
            return self._push_once()

    def _push_once(self) -> bool:
        series = parse_exposition(self.generator.metrics_text())
        if not series:
            return True
        ts_ms = int(time.time() * 1000)
        body = snappy_block_encode(
            encode_write_request([(lab, v, ts_ms) for lab, v in series])
        )
        headers = {
            "Content-Type": "application/x-protobuf",
            "Content-Encoding": "snappy",
            "X-Prometheus-Remote-Write-Version": "0.1.0",
        }
        if self.tenant_header:
            headers["X-Scope-OrgID"] = self.tenant_header
        from ..chaos import plane as chaos_plane

        if chaos_plane.tap("rpc.remotewrite", key=self.url) is chaos_plane.DROP:
            self.failures += 1  # push silently lost downstream
            return False
        try:
            req = urllib.request.Request(self.url, data=body, headers=headers)
            with urllib.request.urlopen(req, timeout=self.timeout_s):
                pass
            self.pushes += 1
            return True
        except Exception:
            self.failures += 1
            return False

    def start(self) -> None:
        def loop():
            while not self._stop.wait(self.interval_s):
                self.push_once()

        self._thread = threading.Thread(target=loop, daemon=True, name="remote-write")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
