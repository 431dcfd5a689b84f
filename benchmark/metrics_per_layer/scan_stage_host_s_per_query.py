"""Host seconds a query spends staging its fused chunks for the device: the
``scan.stage`` spans (io/parquet_native.py ``chunk_to_device``, one a chunk
the fused decode takes: the sorted string dictionary, the page merge, the
segment table, the padding and every ``jnp.asarray`` put, up to the
dispatch of the decode), summed over the scan's threads, mean a query of
the window. None where the program has no such span."""

from benchmark.metrics_per_layer._spans import mean_seconds_a_query


def read(ctx):
    return mean_seconds_a_query(ctx, "scan.stage")
