"""After the window, off the clock: SIGKILL the server, restart it on the
same directories and port, and read back `traces` acknowledged traces -- a
seeded sample of those acknowledged at least `min_age_s` before the kill
(the WAL's fsync interval is <= 0.25 s), the newest such one always among
them. An acknowledged write must be readable after a kill."""
import random
import time

from benchmarks.lib import harness as H
from benchmarks.lib.server import Client


def run(cell_pass, spec):
    env, server = cell_pass.env, cell_pass.server
    acked = env.push_log.older_than(spec["min_age_s"])
    if not acked:
        return [H.result_record(
            {"shape": "kill_restart_readback", "i": -1}, "after", 0, 0.0, 0.0,
            ok=False, detail="nothing was acknowledged before the kill")], {}
    rnd = random.Random(f"{env.seed}-kill-restart")
    sample = [acked[-1]] + [acked[rnd.randrange(len(acked))]
                            for _ in range(spec["traces"] - 1)]
    server.kill()
    t0 = time.perf_counter()
    ready_s = server.start()
    H.log(f"restarted after SIGKILL: ready in {ready_s:.1f}s")
    cl = Client(server.port, timeout=120)
    out = []
    for index, base_ns, _ in sample:
        op = {"shape": "find_pushed", "i": -1, "index": index,
              "base_ns": base_ns, "trace": rnd.randrange(env.push_template.T)}
        t_send = time.perf_counter()
        status, data = cl.request(
            "GET", "/api/traces/" + env.push_template.trace_id(index, op["trace"]))
        out.append(H.result_record(op, "after", status, t_send,
                                   time.perf_counter(), data=data))
    cl.close()
    return out, {"restart_ready_s": ready_s,
                 "restart_readback_s": time.perf_counter() - t0,
                 "acked_requests": len(acked)}
