"""`rate_service` over one block taken by `lib/coldutil.py`'s schedule over the
configuration's `block_popularity`, not by a draw: every seed sends the same
(shape, block) list. Request and check are `rate_service`'s own."""
from benchmarks.lib import coldutil
from benchmarks.shapes import rate_service as one

KIND = one.KIND
SCAN = one.SCAN
request = one.request
check = one.check


def build(rnd, env, params):
    return coldutil.build_over(one, "rate_service", rnd, env, params)
