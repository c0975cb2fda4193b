"""`rate_service` over the newest N whole blocks (lib/rangeutil.py): one
series, every bucket equal to the sum of the oracle's counts over every
block the range overlaps."""
from benchmarks.lib import rangeutil as R
from benchmarks.shapes import rate_service as one

KIND = one.KIND
SCAN = one.SCAN
request = one.request
check = one.check


def build(rnd, env, params):
    n, v, win = R.draw(rnd, env, params, "rate_service_range", 64)
    return {"block": 0, "n": n, "svc": f"svc-{v:03d}", **win}
