"""Seconds a query's threads spend in blocking device-to-host reads and in
the broadcast wait, summed over threads, mean a query of the window: the
spans ``sync.count`` (a row count that was still a device scalar),
``sync.status`` (the chained aggregate's one read), ``sync.matched`` (a
join's matched-rows mask) and ``broadcast.wait`` (exec/broadcast.py)."""

from benchmark.metrics_per_layer._spans import mean_seconds_a_query


def read(ctx):
    return mean_seconds_a_query(ctx, "sync.", "broadcast.wait")
