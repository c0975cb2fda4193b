"""Time inside `rows:materialize` stages (block/reader.materialize_traces:
rows -> wire traces for exact verify) over the searches that were traced: the
part of verify_ms_per_search that is row materialisation, not evaluation. The
stage's extent, not its self time: the chunk reads it triggers
(`stream:fetch`, `stream:decompress`) nest inside it and are what it costs."""
from benchmarks.lib import stages

ROOTS = ("frontend.search",)


def read(ctx):
    return stages.span_ms_per_root(ctx, ("rows:materialize",), ROOTS, extent=True)
