"""The reply's cost inside a served query, mean a query of the window:
``endpoint.encode`` (a result batch to an Arrow IPC body, and its CRC) plus
``endpoint.send`` (queueing the frame for the connection thread, which
blocks while the stream's byte budget is full), summed over its batches."""

from benchmark.metrics_per_layer._spans import mean_seconds_a_query


def read(ctx):
    s = mean_seconds_a_query(ctx, "endpoint.encode", "endpoint.send")
    return None if s is None else s * 1e3
