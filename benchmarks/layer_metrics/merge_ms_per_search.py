"""What putting a multi-block answer together costs a traced search: self
time of `search:merge` (the frontend's cross-job merge, from the first job's
result to the last one merged or cancelled) and of `topk:collect` (the
cross-block candidate collect of one block-batch job: `k` escalation, the
map back to (block, trace), `_candidates`; its `select` launches and
`verify` leaves are children and not in it). The self-trace read-back carries
no attributes, so every `topk:collect` of a search counts, one-block groups
too: in a cell whose every job is a block batch they are the same code.
Nothing where the program writes no `search:merge` span."""
from benchmarks.lib import readers as R, stages

ROOTS = ("frontend.search",)


def read(ctx):
    if not R.spans_named(ctx, "search:merge", ROOTS):
        return None
    return stages.span_ms_per_root(ctx, ("search:merge", "topk:collect"), ROOTS)
