"""The server's own probe of the host-device round trip: `reduce` and sync
timing switch path at 2.0 ms, so it says which path a run took."""


def read(ctx):
    return ctx["kernels_after"].get("device", {}).get("link_rtt_ms")
