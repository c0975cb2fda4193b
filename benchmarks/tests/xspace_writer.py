"""A minimal writer of the profiler's XSpace protobuf (tsl xplane.proto), for
the tests' fixture: planes -> lines -> events with names, starts and
durations, nothing else."""

from __future__ import annotations


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, wire: int, payload: bytes) -> bytes:
    return _varint(num << 3 | wire) + payload


def _int(num: int, v: int) -> bytes:
    return _field(num, 0, _varint(v))


def _bytes(num: int, v: bytes) -> bytes:
    return _field(num, 2, _varint(len(v)) + v)


def encode_xspace(planes: list[dict]) -> bytes:
    """planes: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}] -> serialized XSpace."""
    out = b""
    for pid, p in enumerate(planes, 1):
        names: dict[str, int] = {}
        lines = b""
        for lid, ln in enumerate(p["lines"], 1):
            evs = b""
            t0 = min((s for _, s, _ in ln["events"]), default=0)
            for name, s, d in ln["events"]:
                mid = names.setdefault(name, len(names) + 1)
                evs += _bytes(4, _int(1, mid) + _int(2, int((s - t0) * 1000))
                              + _int(3, int(d * 1000)))
            lines += _bytes(3, _int(1, lid) + _bytes(2, ln["name"].encode())
                            + _int(3, int(t0)) + evs)
        meta = b"".join(
            _bytes(4, _int(1, mid) + _bytes(2, _int(1, mid) + _bytes(2, n.encode())))
            for n, mid in names.items())
        out += _bytes(1, _int(1, pid) + _bytes(2, p["name"].encode()) + lines + meta)
    return out
