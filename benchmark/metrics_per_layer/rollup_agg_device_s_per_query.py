"""Device seconds a query spends in the ROLLUP and its aggregate: programs
named ``jit_srt_ExpandExec*`` and ``jit_srt_HashAggregateExec*`` inside the
traced span, over the queries the span holds."""

from benchmark.metrics_per_layer import _rollup


def read(ctx):
    return _rollup.seconds_a_query(ctx, _rollup.ROLLUP_AGG)
