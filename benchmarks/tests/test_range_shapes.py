"""The range shapes (PR 34): the seeded list of `range-mix`, what a range's
window covers, that nothing repeats, and that warm-up's `per_block` step
reaches every (shape, N) pair whatever the seed."""
import collections
import json
import os
import random
import urllib.parse

import pytest

from benchmarks.lib import harness as H, rangeutil as R, shapeutil as U

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOUR, GAP = 3600, 180


def fake_env(seed, blocks=32):
    """32 one-hour blocks dated as lib/corpus.py dates them: block b starts
    (b + 1) x 3,780 s before `top`, its last second 3,602 s later."""
    config = H.load_json(os.path.join(BENCH, "configs", "chip1-32hourly.json"))
    top = 1_700_000_000 - 1_700_000_000 % HOUR
    manifest = {"blocks": [
        {"index": b, "n_traces": 18750, "n_spans": 18750 * 69,
         "start_s": top - (b + 1) * (HOUR + GAP),
         "end_s": top - (b + 1) * (HOUR + GAP) + 3602, "oracle": ""}
        for b in range(blocks)]}
    return H.Env(config, {"name": "range-mix"}, manifest, seed)


def search_stream():
    mix = H.load_json(os.path.join(BENCH, "mixes", "range-mix.json"))
    return mix, next(s for s in mix["streams"] if s["name"] == "search")


def test_the_configuration_is_the_hourly_blocklist():
    cfg = H.load_json(os.path.join(BENCH, "configs", "chip1-32hourly.json"))
    assert (cfg["blocks"], cfg["source_blocks"], cfg["reduced"]) == (32, 336, ["blocks"])
    pop = cfg["corpus"]["block_popularity"]
    assert len(pop) == 32 and pop == sorted(pop, reverse=True)
    assert pop[1] / pop[0] == pytest.approx(0.85, rel=1e-3)
    base = H.load_json(os.path.join(BENCH, "configs", "chip1-4block.json"))
    for k in ("spans_per_trace", "resources", "attrs_per_span", "attribute_keys",
              "attribute_values", "services", "span_names", "gap_s"):
        assert cfg["corpus"][k] == base["corpus"][k]  # row widths unchanged
    assert cfg["corpus"]["traces_per_block"] * 8 == base["corpus"]["traces_per_block"]
    assert cfg["guarantees"] == base["guarantees"]
    assert cfg["server_args"] == [] and cfg["chips"] == 1


def test_the_mix_is_what_the_issue_asks():
    mix, st = search_stream()
    assert (st["loop"], st["clients"], st["ops"], st["timeout_s"]) == ("closed", 4, 6000, 120)
    assert {s["shape"]: s["weight"] for s in st["shapes"]} == {
        "attr_eq_range": 0.45, "duration_gt_range": 0.20,
        "rate_service_range": 0.20, "tag_service_range": 0.15}
    for s in st["shapes"]:
        assert s["params"]["blocks"] == list(R.BLOCKS)
        assert s["params"]["weights"] == list(R.WEIGHTS)
    find = next(s for s in mix["streams"] if s["name"] == "find")
    assert (find["loop"], find["rate_per_s"], find["senders"], find["timeout_s"]) == (
        "open", 2, 16, 60)
    assert mix["compile_pass"] is True and "compiles_allowed" not in mix
    assert mix["events"] == [] and mix["after_window"] == []
    mean = sum(n * w for n, w in zip(R.BLOCKS, R.WEIGHTS))
    assert mean == pytest.approx(5.66)  # "mean 5.7 blocks"


@pytest.mark.parametrize("seed", [1, 2147610001])
def test_n_follows_its_weights_and_the_window_covers_n_blocks(seed):
    _, st = search_stream()
    env = fake_env(seed)
    ops = H.build_ops("range-mix", st, env, n=4000)
    by_shape = collections.Counter(o["shape"] for o in ops)
    assert by_shape == {"attr_eq_range": 1800, "duration_gt_range": 800,
                        "rate_service_range": 800, "tag_service_range": 600}
    ns = collections.Counter(o["n"] for o in ops)
    for n, w in zip(R.BLOCKS, R.WEIGHTS):
        assert abs(ns[n] - w * len(ops)) <= 4  # within one draw a shape
    for shape in by_shape:  # and inside every shape, from its first requests on
        first = [o["n"] for o in ops if o["shape"] == shape][:20]
        assert [first.count(n) for n in R.BLOCKS] == [7, 5, 4, 2, 2]
    for o in ops:
        assert o["block"] == 0
        assert U.blocks_overlapping(env, o["start"], o["end"]) == list(range(o["n"]))
        assert o["end"] == env.manifest["blocks"][0]["end_s"]
        off = env.manifest["blocks"][o["n"] - 1]["start_s"] - o["start"]
        assert 0 <= off < R.OFFSETS
        assert env.spans_covered(o) == o["n"] * 18750 * 69
        # the offset moves no rate() into a 65th bucket of a one-block range
        if o["n"] == 1:
            lo = o["start"] // 60 * 60
            assert -(-(o["end"] - lo) // 60) <= 64


def test_no_request_repeats_another_inside_a_run():
    """The result cache keys on query + exact start / end: no two requests
    of a run share all three, though 64 services x 5 ranges are few."""
    _, st = search_stream()
    env = fake_env(5)
    seen = set()
    for o in H.build_ops("range-mix", st, env, n=6000):
        method, path, _, _ = H.load_plugin("shapes", o["shape"]).request(o, env)
        assert path not in seen
        seen.add(path)


def test_a_seed_gives_the_same_list_again_and_another_seed_another():
    _, st = search_stream()
    a = H.build_ops("range-mix", st, fake_env(7), n=200)
    b = H.build_ops("range-mix", st, fake_env(7), n=200)
    c = H.build_ops("range-mix", st, fake_env(8), n=200)
    assert json.dumps(a) == json.dumps(b) != json.dumps(c)
    # the same work for every seed: shapes and ranges interleave alike,
    # operands and offsets differ
    assert [(o["shape"], o["n"]) for o in a] == [(o["shape"], o["n"]) for o in c]


@pytest.mark.parametrize("seed", [0, 3, 2147610123])
def test_warm_up_sends_every_shape_over_every_n_whatever_the_seed(seed):
    """harness.warm_up's `per_block` step forces block 0 .. 31 in turn; the
    range shapes map it onto the list of N."""
    mix, _ = search_stream()
    step = next(s for s in mix["warmup"] if s["step"] == "per_block")
    env = fake_env(seed)
    rnd = random.Random(f"{seed}-range-mix-warm")
    pairs = set()
    for spec in step["shapes"]:
        mod = H.load_plugin("shapes", spec["shape"])
        for b in range(len(env.manifest["blocks"])):
            env.force_block = b
            op = mod.build(rnd, env, spec.get("params", {}))
            pairs.add((spec["shape"], op["n"]))
    env.force_block = None
    assert pairs == {(s["shape"], n) for s in step["shapes"] for n in R.BLOCKS}
    assert len(pairs) == 20
    # and then finds, as the other read mixes do
    assert {"step": "ops", "stream": "find", "count": 8} in mix["warmup"]


def test_tag_limit_is_the_traces_covered_plus_100():
    env = fake_env(1)
    mod = H.load_plugin("shapes", "tag_service_range")
    env.force_block = 2  # -> N = 6
    op = mod.build(random.Random(1), env, {})
    env.force_block = None
    _, path, _, _ = mod.request(op, env)
    q = urllib.parse.parse_qs(urllib.parse.urlparse(path).query)
    assert op["n"] == 6 and int(q["limit"][0]) == 6 * 18750 + 100


def test_an_offset_that_reaches_the_next_block_is_refused():
    env = fake_env(1)
    with pytest.raises(ValueError):
        R.window(env, 3, 178)
    assert R.window(env, 32, 178)["start"] == env.manifest["blocks"][31]["start_s"] - 178


def test_range_shapes_keep_their_siblings_scan_and_checks():
    for name in ("attr_eq", "duration_gt", "rate_service"):
        one = H.load_plugin("shapes", name)
        rng = H.load_plugin("shapes", name + "_range")
        assert rng.SCAN == one.SCAN and rng.check is one.check and rng.KIND == one.KIND
    assert not hasattr(H.load_plugin("shapes", "tag_service_range"), "SCAN")
