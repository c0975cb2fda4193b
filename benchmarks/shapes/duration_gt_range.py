"""`duration_gt` over the newest N whole blocks (lib/rangeutil.py): any 20
distinct members of the union of the oracle's sets."""
from benchmarks.lib import rangeutil as R
from benchmarks.shapes import duration_gt as one

KIND = one.KIND
SCAN = one.SCAN
request = one.request
check = one.check


def build(rnd, env, params):
    lo, hi = params.get("ms", [900, 990])
    n, v, win = R.draw(rnd, env, params, "duration_gt_range", (hi - lo) * 1000)
    return {"block": 0, "n": n, "us": lo * 1000 + v, **win}
