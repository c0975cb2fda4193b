"""`correct` has to come out false when the served path is broken underneath:
the oracle's own answers, put in the server's place, pass every shape's
check; the same answers with one fault planted where an answer is produced
fail it. Over a corpus with two blocks a compaction window, so that a
request covers two blocks and "half of the work left out" is a fault a check
can meet. CPU, tiny sizes, no server."""

import json
import os
import random

import pytest

from benchmarks.lib import corpus, harness as H, shapeutil as U
from benchmarks.tests.test_corpus_layout import small

SEARCH = ["attr_eq", "tag_service", "struct_desc", "duration_gt",
          "attr_eq_range", "tag_service_range", "duration_gt_range"]
RATE = ["rate_service", "rate_service_range"]


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("corpus"))
    saved, corpus.bench_dir = corpus.bench_dir, lambda *p: os.path.join(d, *p)
    try:
        cfg = small(4, blocks_per_window=2)
        cfg["corpus"].update(traces_per_block=600, spans_per_trace=6)
        manifest = corpus.ensure(cfg, "tiny", 11, log=lambda m: None)
    finally:
        corpus.bench_dir = saved
    return H.Env(cfg, {"name": "t"}, manifest, 11)


def an_op(env, shape: str) -> dict:
    """The first op of the shape that has an answer; for a tag search and a
    rate(), one that every block its window covers has an answer for."""
    mod = H.load_plugin("shapes", shape)
    rnd = random.Random(shape)
    for _ in range(2000):
        op = mod.build(rnd, env, {"blocks": [1, 2], "weights": [1, 1], "ms": [1, 900]})
        op.update(shape=shape, i=0)
        covered = U.blocks_overlapping(env, op["start"], op["end"])
        if shape in RATE:
            if all(env.oracle(b).service_spans(op["svc"]).any() for b in covered):
                return op
        elif shape.startswith("tag_service"):
            if all(truth(env, op, [b]) for b in covered):
                return op
        elif truth(env, op):
            return op
    raise AssertionError(f"no operand of {shape} has an answer")


def truth(env, op, blocks=None) -> list[str]:
    """What the oracle answers for a search op (over `blocks`, else every
    block its window overlaps)."""
    fn = {"attr_eq": lambda o: o.traces_attr(op["key"], op["val"]),
          "tag_service": lambda o: o.traces_service(op["svc"]),
          "struct_desc": lambda o: o.traces_descendant(op["key"], op["val"], op["ms"] * 1000),
          "duration_gt": lambda o: o.traces_duration_gt(op["us"]),
          }[op["shape"].replace("_range", "")]
    if blocks is None:
        blocks = U.blocks_overlapping(env, op["start"], op["end"])
    out: set = set()
    for b in blocks:
        out |= fn(env.oracle(b))
    return sorted(out)


def search_body(ids, limit=None) -> bytes:
    return json.dumps({"traces": [{"traceID": i} for i in ids[:limit]]}).encode()


def rate_body(env, op, times=1) -> bytes:
    from benchmarks.shapes.rate_service import STEP_S
    step_ms = STEP_S * 1000
    start_ms = (op["start"] * 1000 // step_ms) * step_ms
    nb = -(-(op["end"] * 1000 - start_ms) // step_ms)
    counts = sum(env.oracle(b).rate_counts(op["svc"], start_ms, step_ms, nb)
                 for b in U.blocks_overlapping(env, op["start"], op["end"]))
    values = [[(start_ms + k * step_ms) / 1000, times * int(c) / STEP_S]
              for k, c in enumerate(counts) if c]
    return json.dumps({"data": {"result": [{"values": values}]}}).encode()


def find_body(env, op, alter=False) -> bytes:
    spans = [{"spanId": s[0], "name": s[1], "startTimeUnixNano": str(s[2]),
              "endTimeUnixNano": str(s[3] + (1 if alter and k == 0 else 0))}
             for k, s in enumerate(sorted(env.oracle(op["block"]).trace_spans(op["sid"])))]
    return json.dumps({"resourceSpans": [{"scopeSpans": [{"spans": spans}]}]}).encode()


def check(env, op, status, body):
    return H.load_plugin("shapes", op["shape"]).check(op, status, body, env)[0]


@pytest.mark.parametrize("shape", SEARCH)
def test_a_search_answer_altered_is_not_correct(env, shape):
    op = an_op(env, shape)
    covered = U.blocks_overlapping(env, op["start"], op["end"])
    assert len(covered) >= 2  # the window's mates are covered whole
    ids = truth(env, op)
    limit = 20 if shape.startswith("duration_gt") else None
    assert check(env, op, 200, search_body(ids, limit))
    assert not check(env, op, 200, search_body(ids[1:] if limit is None else ids[:limit - 1]))
    assert not check(env, op, 200, search_body((["f" * 32] + ids)[:limit] if limit
                                               else ids + ["f" * 32]))
    assert not check(env, op, 500, b"")
    assert not check(env, op, 200, b"{}")
    if shape.startswith("tag_service"):
        # one of the blocks that share the window left out of the answer
        half = truth(env, op, covered[:1])
        assert half != ids and not check(env, op, 200, search_body(half))


@pytest.mark.parametrize("shape", RATE)
def test_a_rate_that_counts_double_is_not_correct(env, shape):
    op = an_op(env, shape)
    assert check(env, op, 200, rate_body(env, op))
    assert not check(env, op, 200, rate_body(env, op, times=2))  # ROADMAP A12's fault
    assert not check(env, op, 200, json.dumps({"data": {"result": []}}).encode())


def test_a_find_altered_and_a_miss_answered_are_not_correct(env):
    mod = H.load_plugin("shapes", "find_hit")
    op = mod.build(random.Random(3), env, {})
    op.update(shape="find_hit", i=0)
    assert check(env, op, 200, find_body(env, op))
    assert not check(env, op, 200, find_body(env, op, alter=True))
    assert not check(env, op, 404, b"")
    miss = {"shape": "find_miss", "i": 0, "id": "0" * 32}
    assert check(env, miss, 404, b"") and not check(env, miss, 200, find_body(env, op))


def test_the_runs_own_pass_over_the_answers_counts_the_fault(env):
    """`H.check_all` is what a run judges its answers with: one planted fault
    among sound answers is one failure, which makes the run not correct."""
    ops = [an_op(env, s) for s in ("attr_eq", "tag_service", "attr_eq_range")]
    results = [H.result_record(op, "window", 200, 0.0, 0.1,
                               data=search_body(truth(env, op))) for op in ops]
    results.append(H.result_record(ops[0], "window", 200, 0.0, 0.1,
                                   data=search_body(truth(env, ops[0])[1:])))
    H.check_all(results, env)
    assert [r["ok"] for r in results] == [True, True, True, False]
    assert all(r["data"] == b"" for r in results)
