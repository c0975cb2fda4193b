"""POST /v1/traces, OTLP/HTTP protobuf: traces x spans from the mix file,
ids from (--seed, request index), spans dated now. An acknowledged request
goes into the push log, from which the read-back shapes and the durability
check draw."""
import time

KIND = "push"


def build(rnd, env, params):
    return {}  # the request index (its place in the stream) is the operand


def request(op, env):
    op["index"] = op["i"]  # the request's place in the stream: unique
    op["base_ns"] = time.time_ns() - 2_000_000_000
    return ("POST", "/v1/traces", env.push_template.body(op["index"],
                                                         op["base_ns"]),
            {"Content-Type": "application/x-protobuf"})


def on_response(op, status, env):
    if status == 200:
        env.push_log.add(op["index"], op["base_ns"])


def check(op, status, body, env):
    return status == 200, "" if status == 200 else f"HTTP {status}: {body[:200]!r}"
