import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from benchmarks.lib import harness as H

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fake_env(seed):
    config = H.load_json(os.path.join(BENCH, "configs", "chip1-4block.json"))
    manifest = {"blocks": [{"index": b, "n_traces": 1500, "n_spans": 12000,
                            "start_s": 1000 + 4000 * (3 - b),
                            "end_s": 4601 + 4000 * (3 - b), "oracle": ""}
                           for b in range(4)]}
    return H.Env(config, {"name": "t"}, manifest, seed)


@pytest.mark.parametrize("mix_name", ["read-mix", "find-mix", "write-live"])
def test_seeded_request_list_is_identical_across_calls(mix_name):
    mix = H.load_json(os.path.join(BENCH, "mixes", mix_name + ".json"))
    for stream in mix["streams"]:
        a = H.build_ops(mix_name, stream, fake_env(7), n=120)
        b = H.build_ops(mix_name, stream, fake_env(7), n=120)
        c = H.build_ops(mix_name, stream, fake_env(8), n=120)
        assert json.dumps(a) == json.dumps(b)
        assert [o["shape"] for o in a] == [o["shape"] for o in c]  # same interleaving
        if stream["name"] != "push":
            assert json.dumps(a) != json.dumps(c)  # other operands
        if stream["loop"] == "open":
            assert (H.due_times(mix_name, stream, 7, 50)
                    == H.due_times(mix_name, stream, 7, 50))


def test_shape_schedule_follows_the_weights():
    shapes = [{"weight": 0.4}, {"weight": 0.2}, {"weight": 0.15},
              {"weight": 0.15}, {"weight": 0.1}]
    sched = H.shape_schedule(shapes, 200)
    assert [sched.count(k) for k in range(5)] == [80, 40, 30, 30, 20]
    assert [sched[:20].count(k) for k in range(5)] == [8, 4, 3, 3, 2]


def test_operands_do_not_repeat_inside_a_run():
    mix = H.load_json(os.path.join(BENCH, "mixes", "read-mix.json"))
    ops = H.build_ops("read-mix", mix["streams"][0], fake_env(3), n=400)
    keys = [json.dumps({k: v for k, v in o.items() if k != "i"}, sort_keys=True)
            for o in ops]
    assert len(set(keys)) >= 0.97 * len(keys)


class _Slow(BaseHTTPRequestHandler):
    def do_GET(self):
        if self.path == "/stall":
            time.sleep(0.5)
        self.send_response(200)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"ok")

    def log_message(self, *a):
        pass


def test_open_loop_latency_counts_from_the_due_time(monkeypatch):
    """One sender, a request that stalls 0.5 s: the requests due during the
    stall are charged the wait, although each is answered at once."""
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    threading.Thread(target=srv.serve_forever, daemon=True).start()

    class Shape:
        KIND = "find"

        @staticmethod
        def request(op, env):
            return "GET", "/stall" if op["i"] == 0 else "/fast", None, {}

    monkeypatch.setattr(H, "load_plugin", lambda kind, name: Shape)
    st = H.StreamState.__new__(H.StreamState)
    st.spec = {"name": "s", "loop": "open", "rate_per_s": 10, "senders": 1}
    st.env, st.ops = None, [{"shape": "x", "i": i} for i in range(4)]
    st.dues = [0.05, 0.15, 0.25, 0.35]
    st.cursor, st.window_from, st.dues_base, st.skipped = 0, 0, 0.0, 0
    st.lock, st.results = threading.Lock(), []
    t0 = time.perf_counter()
    for t in H.run_open(st, srv.server_address[1], t0, t0 + 1.0, "window"):
        t.join(timeout=10)
        assert not t.is_alive()
    srv.shutdown()
    res = sorted(st.results, key=lambda r: r["op"]["i"])
    assert len(res) == 4
    for r, due in zip(res, st.dues):
        assert r["t_due"] == pytest.approx(t0 + due)
    service = [r["t_done"] - r["t_send"] for r in res]
    from_due = [r["t_done"] - r["t_due"] for r in res]
    assert service[1] < 0.2 and from_due[1] > 0.35   # waited behind the stall
    assert from_due[0] >= 0.5
