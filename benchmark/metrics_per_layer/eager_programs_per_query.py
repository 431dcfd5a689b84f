"""Device programs a query runs that no fused stage owns: runs in the
traced span whose module name lacks ``srt_`` (runtime/fuse.py names every
kernel's program ``jit_srt_<kernel>``; what is left are eager ``jnp`` calls,
one tiny program and one Python dispatch each), over the queries the span
holds. ``dispatches_per_query`` counts the other kind."""

from benchmark.metrics_per_layer._programs import (program_runs,
                                                   queries_in_span)


def read(ctx):
    runs, queries = program_runs(ctx), queries_in_span(ctx)
    if runs is None or queries <= 0:
        return None
    return sum("srt_" not in name for name, _ in runs) / queries
