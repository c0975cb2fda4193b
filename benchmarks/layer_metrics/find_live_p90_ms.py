"""p90, from the due time, of the find stream beside the pushers (live head,
cut block, corpus blocks, misses)."""
from benchmarks.layer_metrics import find_beside_search_p90_ms


def read(ctx):
    return find_beside_search_p90_ms.read(ctx)
