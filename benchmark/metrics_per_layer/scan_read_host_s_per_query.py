"""Host seconds a query spends reading its column chunks: the ``scan.read``
spans (io/parquet_native.py ``read_chunk_pages``, one a chunk, a child of
its ``scan.column``: the file read, the native page scan or the page walk
with its decompression, the dictionary's decode), summed over the scan's
threads, mean a query of the window. None where the program has no such
span."""

from benchmark.metrics_per_layer._spans import mean_seconds_a_query


def read(ctx):
    return mean_seconds_a_query(ctx, "scan.read")
