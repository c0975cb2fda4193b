"""Ingester clients over the role boundary.

`client_registry` resolves an instance addr to a client: in-process
objects for the single binary, HTTPIngesterClient for `http://...`
addrs (the reference's gRPC ingester client seam,
modules/distributor/distributor.go:148-153 factory).

Wire format: the DATA plane (segment push, generator forward, find
responses) runs on length-prefixed binary frames (transport/frames.py,
<5% overhead, optional whole-body zstd -- the reference's gRPC+snappy
analog); small control payloads stay JSON. Legacy JSON+base64 remains
accepted server-side, and pushes retry as JSON once when a pre-frames
server rejects the binary body (rolling upgrades).
"""

from __future__ import annotations

import base64
import json
import urllib.error
import urllib.request

from ..db.search import SearchRequest, SearchResponse
from ..util.kerneltel import TEL
from ..wire import otlp_json
from ..wire.model import Trace


class TransportError(Exception):
    def __init__(self, status: int, msg: str):
        super().__init__(msg)
        self.status = status


def _raise_http_error(e: urllib.error.HTTPError):
    """Shared HTTPError -> typed exception mapping (ingester-side limit
    errors keep their real status for the caller's retry policy)."""
    try:
        msg = json.loads(e.read()).get("error", "")
    except Exception:
        msg = str(e)
    from ..services.distributor import PushError

    raise PushError(e.code, msg) if e.code in (400, 429) else TransportError(e.code, msg)


class HTTPIngesterClient:
    def __init__(self, addr: str, timeout: float = 10.0, token: str = ""):
        self.addr = addr.rstrip("/")
        self.timeout = timeout
        self.token = token

    @staticmethod
    def _chaos_tap(path: str) -> None:
        """RPC chaos seam: injected latency/error/black-hole on every
        ingester-client call (drop surfaces as a transport error -- a
        black-holed request IS a timeout to its caller)."""
        from ..chaos import plane as chaos_plane

        if chaos_plane.tap("rpc.client", key=path) is chaos_plane.DROP:
            raise TransportError(0, "chaos: request black-holed")

    def _post(self, path: str, payload: dict) -> dict:
        self._chaos_tap(path)
        headers = {"Content-Type": "application/json"}
        if self.token:
            headers["X-Tempo-Internal-Token"] = self.token
        req = urllib.request.Request(
            self.addr + path,
            data=json.dumps(payload).encode(),
            headers=headers,
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout) as r:
                body = r.read()
                return json.loads(body) if body else {}
        except urllib.error.HTTPError as e:
            _raise_http_error(e)

    def _post_frames(self, path: str, body: bytes) -> None:
        from . import frames

        self._chaos_tap(path)
        headers = {"Content-Type": frames.CONTENT_TYPE}
        if self.token:
            headers["X-Tempo-Internal-Token"] = self.token
        req = urllib.request.Request(self.addr + path, data=body, headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=self.timeout) as r:
                r.read()
        except urllib.error.HTTPError as e:
            _raise_http_error(e)
        except urllib.error.URLError as e:
            raise TransportError(0, str(e))

    # ------------------------------------------------- Pusher (write path)
    def push_segments(self, tenant: str, batch) -> None:
        from . import frames

        try:
            self._post_frames("/internal/push", frames.encode_push(tenant, batch))
        except TransportError:
            # rolling-upgrade interop: a pre-frames server 500s on the
            # binary body; retry once as legacy JSON+base64
            self._post(
                "/internal/push",
                {"tenant": tenant,
                 "batch": [[tid.hex(), s, e, base64.b64encode(seg).decode()]
                           for tid, s, e, seg in batch]},
            )

    def push_generator_blobs(self, tenant: str, blobs: list[bytes]) -> None:
        """Forward traces to a remote metrics-generator as otlp-proto
        bytes sliced from segments (the shuffle-sharded generator write
        path, distributor.go:410-442): zero decode/encode on the send
        side. The legacy-JSON fallback is the only path that must
        decode."""
        from . import frames

        try:
            self._post_frames("/internal/genpush",
                              frames.encode_trace_blobs(tenant, blobs))
        except TransportError:
            from ..wire import otlp_pb

            self._post(
                "/internal/genpush",
                {"tenant": tenant,
                 "traces": [otlp_json.dumps(otlp_pb.decode_trace(b))
                            for b in blobs]},
            )

    # ------------------------------------------------ Querier (read path)
    def find_trace_by_id(self, tenant: str, trace_id: bytes) -> Trace | None:
        """Find over the binary plane: the response body is the raw
        otlp-proto trace (Accept negotiation keeps old servers working)."""
        from ..wire import otlp_pb

        self._chaos_tap("/internal/find")
        headers = {"Content-Type": "application/json",
                   "Accept": "application/x-protobuf"}
        if self.token:
            headers["X-Tempo-Internal-Token"] = self.token
        req = urllib.request.Request(
            self.addr + "/internal/find",
            data=json.dumps({"tenant": tenant, "trace_id": trace_id.hex()}).encode(),
            headers=headers,
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout) as r:
                body = r.read()
                if r.headers.get("Content-Type", "").startswith("application/x-protobuf"):
                    return otlp_pb.decode_trace(body) if body else None
                out = json.loads(body) if body else {}
        except urllib.error.HTTPError as e:
            _raise_http_error(e)
        except urllib.error.URLError as e:
            raise TransportError(0, str(e))
        if not out.get("trace"):
            return None
        return otlp_json.loads(out["trace"])

    def search(self, tenant: str, req: SearchRequest) -> SearchResponse:
        from ..db.search import request_to_dict, response_from_dict

        out = self._post(
            "/internal/search", {"tenant": tenant, "req": request_to_dict(req)}
        )
        return response_from_dict(out)

    def metrics_query_range(self, tenant: str, req):
        """Live-head TraceQL metrics leg against a remote ingester
        (None when it holds nothing for the tenant)."""
        from ..db.metrics_exec import (
            request_to_dict as metrics_request_to_dict,
            response_from_dict as metrics_response_from_dict,
        )

        out = self._post(
            "/internal/metrics",
            {"tenant": tenant, "req": metrics_request_to_dict(req)},
        )
        return metrics_response_from_dict(out) if out else None

    def trace_snapshot(self, tenant: str, trace_id: bytes) -> list[tuple[str, bytes]]:
        """Replica segment snapshot for a quorum read: [(digest, seg)]."""
        out = self._post(
            "/internal/snapshot",
            {"tenant": tenant, "trace_id": trace_id.hex()},
        )
        return [(d, base64.b64decode(seg))
                for d, seg in out.get("segments", [])]


def client_registry(local: dict, token: str = "", timeout: float = 10.0):
    """addr -> client resolver: in-process objects first, HTTP for the
    rest. `timeout` is the per-RPC deadline every HTTP client gets (the
    fleet's replica-write/read deadline knob)."""
    cache: dict[str, HTTPIngesterClient] = {}

    def resolve(addr: str):
        if addr in local:
            return local[addr]
        if addr.startswith("http://") or addr.startswith("https://"):
            c = cache.get(addr)
            if c is None:
                c = cache[addr] = HTTPIngesterClient(addr, timeout=timeout,
                                                     token=token)
            return c
        raise KeyError(f"unknown instance addr {addr!r}")

    return resolve


# ----------------------------------------------------------- server side


def handle_internal(app, path: str, payload: dict, raw_body: bytes = b"",
                    content_type: str = "", accept: str = ""):
    """Dispatch one internal-API request against this process's modules.
    Returns (status, dict) or (status, (bytes, content_type)) for binary
    responses. Binary-frame bodies (transport/frames.py) arrive with
    payload={} and the raw body; JSON bodies keep the legacy dict path
    so mixed-version fleets interoperate."""
    from . import frames

    binary = content_type.startswith(frames.CONTENT_TYPE)
    if binary and path == "/internal/push":
        if app.ingester is None:
            return 404, {"error": f"target {app.cfg.target} hosts no ingester"}
        tenant, batch = frames.decode_push(raw_body)
        app.ingester.push_segments(tenant, batch)
        return 200, {}
    if binary and path == "/internal/genpush":
        if app.generator is None:
            return 404, {"error": f"target {app.cfg.target} hosts no generator"}
        tenant, traces = frames.decode_traces(raw_body)
        app.generator.push(tenant, traces)
        return 200, {}
    if path == "/internal/chaos":
        # runtime fault-rule control (tempo-tpu-cli chaos inject):
        # {"rules": [...], "seed": n} swaps the plane, {"clear": true}
        # tears it down. Token-gated like every /internal route. Note:
        # the backend seam's wrapper interposes at TempoDB build time,
        # so rules injected into a process that started UNARMED reach
        # the rpc/device/wal/gossip seams only.
        from ..chaos import plane as chaos_plane

        try:
            if payload.get("clear"):
                chaos_plane.clear()
            elif "rules" in payload or "seed" in payload:
                rules, seed = chaos_plane.parse_rules(payload)
                chaos_plane.configure(rules, seed=seed)
        except (ValueError, TypeError) as e:
            return 400, {"error": f"bad chaos rules: {e}"}
        return 200, chaos_plane.status()
    if path == "/internal/jobs/poll":
        # remote querier pull (services/worker.py) against this frontend
        if app.frontend is None:
            return 404, {"error": f"target {app.cfg.target} hosts no frontend"}
        job = app.frontend.poll_job(wait_s=float(payload.get("wait_s", 5.0)),
                                    worker_id=payload.get("worker_id", ""),
                                    device=payload.get("device"),
                                    staged_blocks=payload.get("staged_blocks"))
        if not job:
            return 200, {}
        # the frontend's side of the wire: a job encoded once, here
        with TEL.stage("job:encode", kind=job["kind"]) as st:
            body = json.dumps(job).encode()
            st.attrs["bytes"] = len(body)
        TEL.add_wire_bytes(len(body))
        return 200, (body, "application/json")
    if path == "/internal/jobs/result":
        if app.frontend is None:
            return 404, {"error": f"target {app.cfg.target} hosts no frontend"}
        TEL.add_wire_bytes(len(raw_body or b""))
        app.frontend.complete_job(
            payload.get("id", ""), bool(payload.get("ok")),
            result=payload.get("result"), error=payload.get("error", ""),
            retryable=bool(payload.get("retryable")),
            self_spans=payload.get("self_spans"),
            skipped=bool(payload.get("skipped")),
            received_unix=float(payload.get("received_unix") or 0.0),
            posted_unix=float(payload.get("posted_unix") or 0.0),
        )
        return 200, {}
    if path == "/internal/genpush":
        if app.generator is None:
            return 404, {"error": f"target {app.cfg.target} hosts no generator"}
        traces = [otlp_json.loads(t) for t in payload.get("traces", [])]
        app.generator.push(payload.get("tenant", ""), traces)
        return 200, {}
    if app.ingester is None:
        return 404, {"error": f"target {app.cfg.target} hosts no ingester"}
    tenant = payload.get("tenant", "")
    if path == "/internal/push":
        batch = [
            (bytes.fromhex(tid), s, e, base64.b64decode(seg))
            for tid, s, e, seg in payload.get("batch", [])
        ]
        app.ingester.push_segments(tenant, batch)
        return 200, {}
    if path == "/internal/find":
        tr = app.ingester.find_trace_by_id(tenant, bytes.fromhex(payload["trace_id"]))
        if "application/x-protobuf" in accept:
            from ..wire import otlp_pb

            body = otlp_pb.encode_trace(tr) if tr is not None else b""
            return 200, (body, "application/x-protobuf")
        return 200, {"trace": otlp_json.dumps(tr) if tr is not None else None}
    if path == "/internal/search":
        from ..db.search import request_from_dict, response_to_dict

        resp = app.ingester.search(tenant, request_from_dict(payload.get("req", {})))
        return 200, response_to_dict(resp)
    if path == "/internal/snapshot":
        # quorum-read replica snapshot: raw segments + content digests
        segs = app.ingester.trace_snapshot(tenant, bytes.fromhex(payload["trace_id"]))
        return 200, {"segments": [[d, base64.b64encode(s).decode()]
                                  for d, s in segs]}
    if path == "/internal/metrics":
        # live-head TraceQL metrics leg (querier merges it with blocks)
        from ..db.metrics_exec import (
            request_from_dict as metrics_request_from_dict,
            response_to_dict as metrics_response_to_dict,
        )

        resp = app.ingester.metrics_query_range(
            tenant, metrics_request_from_dict(payload.get("req", {})))
        return 200, (metrics_response_to_dict(resp) if resp is not None else {})
    return 404, {"error": f"no internal route {path}"}
