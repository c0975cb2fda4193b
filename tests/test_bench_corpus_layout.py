"""The corpus builder's layout cases in tier-1: every case of
`benchmarks/tests/test_corpus_layout.py` (PR 40: tenants, blocks a compaction
window, workers in batches, the `per_block` warm-up's `max_parallel`), taken
from that module and not copied -- tier-1 does not collect `benchmarks/tests`
-- and the one case the un-reduced deployment adds: `chip1-12block` is the
first configuration whose workers do not all build at once."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import corpus  # noqa: E402
from benchmarks.tests import test_corpus_layout as layout  # noqa: E402

in_tmp = layout.in_tmp  # the fixture its cases ask for

# its test functions under their own names, parametrisation and all
globals().update({name: fn for name, fn in vars(layout).items()
                  if name.startswith("test_") and callable(fn)})


@pytest.mark.parametrize("name,at_once", [
    ("chip1-12block", 8), *[(n, None) for n in layout.CONFIGS]])
def test_only_the_unreduced_deployment_builds_in_batches(name, at_once):
    """Twelve full-width workers as 8 + 4 under the fixed 24 GiB bound (what
    the chip machine's host built in 43 s, PERF.md); the four older
    configurations every block at once, so their set-up does not move."""
    sz = corpus.sizes(layout.config(name), "full")
    n = corpus.resolve_workers(sz)
    assert n == (at_once or sz["blocks"])
    if at_once:
        assert sz["blocks"] == 12 and [n, sz["blocks"] - n] == [8, 4]
        assert corpus.cache_key(sz) == "b12-t150000x69-g180"
