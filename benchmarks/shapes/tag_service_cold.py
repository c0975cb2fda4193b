"""`tag_service` over one block taken by `lib/coldutil.py`'s schedule over the
configuration's `block_popularity`, not by a draw: every seed sends the same
(shape, block) list. Request and check are `tag_service`'s own."""
from benchmarks.lib import coldutil
from benchmarks.shapes import tag_service as one

KIND = one.KIND
request = one.request
check = one.check


def build(rnd, env, params):
    return coldutil.build_over(one, "tag_service", rnd, env, params)
