"""Device seconds a query spends in its window: programs named
``jit_srt_WindowExec*`` inside the traced span, over the queries the span
holds. No reading where the trace holds no such program (a commit whose
window runs as eager programs)."""

from benchmark.metrics_per_layer import _rollup


def read(ctx):
    return _rollup.seconds_a_query(ctx, _rollup.WINDOW)
