"""`attr_eq` over the newest N whole blocks (lib/rangeutil.py). Exact set:
the union of the oracle's answers over every block the range overlaps."""
from benchmarks.lib import rangeutil as R
from benchmarks.shapes import attr_eq as one

KIND = one.KIND
SCAN = one.SCAN
request = one.request
check = one.check


def build(rnd, env, params):
    n, v, win = R.draw(rnd, env, params, "attr_eq_range", 99 * 5000)
    return {"block": 0, "n": n, "key": f"attr.key{1 + v // 5000:03d}",
            "val": f"value-{v % 5000:05d}", **win}
