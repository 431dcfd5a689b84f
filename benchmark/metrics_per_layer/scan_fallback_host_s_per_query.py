"""Host seconds a query spends on the chunks that leave the fused decode:
the ``scan.fallback`` spans (io/parquet_native.py ``read_row_group_device``:
pyarrow's decode of the chunk and its dense upload; the span counts
``reason``), summed over the scan's threads, mean a query of the window.
0 where the program reads its chunks under ``scan.read`` spans and none
fell back; None where it has no such span at all."""

from benchmark.metrics_per_layer._spans import seconds, window_queries


def read(ctx):
    queries = window_queries(ctx)
    if not queries or not any(s["name"] == "scan.read"
                              for q in queries for s in q["spans"]):
        return None
    return sum(seconds(q["spans"], "scan.fallback")
               for q in queries) / len(queries)
