"""Device seconds a query spends in Q18's aggregates: programs named
``jit_srt_HashAggregateExec*`` inside the traced span, over the queries the
span holds. The aggregate by ``l_orderkey`` (1.5 M groups at SF 1) is nearly
all of it; the five-key group-by after the joins (a few hundred rows) is
counted too."""

from benchmark.metrics_per_layer import _orderkey


def read(ctx):
    return _orderkey.seconds_a_query(ctx)
