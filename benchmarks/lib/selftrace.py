"""Read the server's self-traces back over HTTP from the self tenant."""

from __future__ import annotations

import time
import urllib.parse

from .server import Client

TENANT_HEADER = "X-Scope-OrgID"


def read_back(port: int, tenant: str, since_unix: float, limit: int = 2000,
              roots=("frontend.search", "frontend.metrics_query_range")
              ) -> list[list[dict]]:
    """Traces of the self tenant started since `since_unix`, each a list of
    {"id", "parent", "name", "start", "end"} (seconds)."""
    cl = Client(port, timeout=120)
    hdr = {TENANT_HEADER: tenant}
    q = urllib.parse.urlencode({"q": "{ true }", "limit": limit,
                                "start": int(since_unix) - 1,
                                "end": int(time.time()) + 60})
    status, out = cl.get_json("/api/search?" + q, headers=hdr)
    traces = []
    for t in (out or {}).get("traces", []):
        if roots and t.get("rootTraceName") not in roots:
            continue
        status, doc = cl.get_json("/api/traces/" + t["traceID"], headers=hdr)
        if status != 200 or not doc:
            continue
        spans = []
        for rs in doc.get("resourceSpans", []):
            for ss in rs.get("scopeSpans", []):
                for sp in ss.get("spans", []):
                    spans.append({"id": sp["spanId"],
                                  "parent": sp.get("parentSpanId", ""),
                                  "name": sp["name"],
                                  "start": int(sp["startTimeUnixNano"]) / 1e9,
                                  "end": int(sp["endTimeUnixNano"]) / 1e9})
        traces.append(spans)
    cl.close()
    return traces
