"""How late the open-loop generator released a request, against its due time,
p99: a starved generator, not the server, when this is large. (The wait for
a free sender behind a stalled server is the server's and counts in the
latency from the due time, not here.)"""
from benchmarks.lib import stats


def read(ctx):
    late = [(r.get("t_disp", r["t_send"]) - r["t_due"]) * 1e3
            for st in ctx["streams"].values() if st["spec"]["loop"] == "open"
            for r in st["results"]]
    return stats.percentile(late, 0.99)
