"""Bytes that cross the host between a child and its mesh exchange, a query:
``d2h_bytes`` of the ``MeshExchange.map`` spans (the child drained to Arrow)
plus ``h2d_bytes`` of the ``MeshExchange.ingest`` spans (the shards uploaded
again, each at its bucket), mean over the window's queries. What a
device-resident map side takes to 0."""

from benchmark import mesh_bytes
from benchmark.metrics_per_layer._spans import window_queries


def read(ctx):
    queries = window_queries(ctx)
    if not queries:
        return None
    total = sum(mesh_bytes.host_hop_bytes(q["spans"]) for q in queries)
    if total <= 0:
        return None
    return total / len(queries) / 1e6
