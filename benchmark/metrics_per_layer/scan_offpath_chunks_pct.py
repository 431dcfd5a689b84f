"""Share of the window's column chunks that left the scan's fused decode:
``scan.column`` spans (io/parquet_native.py, one a column of a row group)
whose ``path`` count is ``pages`` (page by page, eager: an RLE run in a
dictionary chunk puts it there) or ``fallback`` (pyarrow), over all of them."""

from benchmark.metrics_per_layer._spans import window_queries


def read(ctx):
    queries = window_queries(ctx)
    if not queries:
        return None
    paths = [s["counts"].get("path") for q in queries for s in q["spans"]
             if s["name"] == "scan.column"]
    if not paths:
        return None
    return 100.0 * sum(p != "fused" for p in paths) / len(paths)
