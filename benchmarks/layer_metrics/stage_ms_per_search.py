"""Self time of staging a block's columns for a search (`stage:read_columns`
+ `stage:assemble` + `stage:upload` stages of ops/stage) over the searches
that were traced: what a staged-cache miss costs, beside staged_hit_share."""
from benchmarks.lib import stages

ROOTS = ("frontend.search",)
NAMES = ("stage:read_columns", "stage:assemble", "stage:upload")


def read(ctx):
    return stages.span_ms_per_root(ctx, NAMES, ROOTS)
