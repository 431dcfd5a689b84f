"""What handing out resident batches costs the host: the ``CachedScan.read``
spans (the catalog's lookup of a cached batch, and its way back to the chip
where it had been demoted) summed a query, mean over the window's queries,
in milliseconds. None without such spans."""

from benchmark.metrics_per_layer._spans import mean_seconds_a_query


def read(ctx):
    secs = mean_seconds_a_query(ctx, "CachedScan.read")
    return None if secs is None else 1e3 * secs
